"""Ahead-of-time compiles for a described v5e chip (no chip attached).

Every Pallas kernel that stays on the TPU dispatch is compiled at the
largest shape its dispatch accepts there, by the TPU compiler that ships
with jaxlib: what Mosaic refuses (an unaligned block, a missing lowering,
too much VMEM) fails here instead of on the chip.  The kernels the
dispatch routes away on TPU must raise under ``impl="kernel"``.

The topology is described inside a module fixture — never at import — so
every xdist worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compression as COMP
from repro.kernels import dispatch
from repro.kernels.cr_reduce import kernel as CRK
from repro.kernels.cr_reduce import ops as CR
from repro.kernels.onebit_ef import ops as OB
from repro.kernels.onebit_ef.kernel import onebit_ef
from repro.kernels.sim_step import kernel as SSK
from repro.kernels.sim_step import ops as SSO
from repro.kernels.swa_attention import ops as SWA
from repro.kernels.swa_attention.kernel import swa_decode_attention
from repro.kernels.topk_ef import compact as TKC
from repro.kernels.topk_ef import ops as TK
from repro.kernels.topk_ef.kernel import topk_ef
from repro.models import moe as MOE


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture
def on_tpu(topo, monkeypatch):
    """Steer the dispatch rule to the described device's platform."""
    monkeypatch.setattr(dispatch, "backend",
                        lambda: topo.devices[0].platform)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# Largest shapes each kernel's TPU dispatch accepts (the VMEM budget binds
# R or d; rows and grid steps are free).  The serve shapes are qwen3-1.7b's
# (8 kv heads of 128, 2 query heads per kv head) over 8 request slots; the
# simulator's are the largest (p, d) of its benchmark grids.
TOPK_R, TOPK_K = 32768, 2048               # ratio 1/16
# the compaction kernel takes any row length: the tied embedding as one row
EMBED_R = 151936 * 2048
ONEBIT_R = 43008
CR_S, CR_R = 4, 32640                      # four workers' sign panels
SIM_P, SIM_D = 32, 512
# the Moonlight cell's grouped expert products: 8,192 tokens x 6 choices
# of sorted rows over the 8 held experts of 2,048 x 1,408
GMM_M, GMM_D, GMM_F, GMM_E = 8192 * 6, 2048, 1408, 8


def _gmm_grad(x, w, sizes):
    return jax.grad(lambda x, w: jnp.sum(MOE.grouped_matmul(
        x, w, sizes).astype(jnp.float32) ** 2), argnums=(0, 1))(x, w)


def _gmm_shapes(k, n):
    return lambda s: (_sds(s, (GMM_M, k), jnp.bfloat16),
                      _sds(s, (GMM_E, k, n), jnp.bfloat16),
                      _sds(s, (GMM_E,), jnp.int32))


KERNELS = {
    "moe.gmm.gate_up": (
        MOE.grouped_matmul, _gmm_shapes(GMM_D, GMM_F),
        lambda: MOE.gmm_why_not(GMM_M, GMM_D, GMM_F)),
    "moe.gmm.down": (
        MOE.grouped_matmul, _gmm_shapes(GMM_F, GMM_D),
        lambda: MOE.gmm_why_not(GMM_M, GMM_F, GMM_D)),
    "moe.gmm.backward": (
        _gmm_grad, _gmm_shapes(GMM_D, GMM_F),
        lambda: MOE.gmm_why_not(GMM_M, GMM_D, GMM_F)),
    "topk_ef": (
        lambda g, e: topk_ef(g, e, k=TOPK_K),
        lambda s: (_sds(s, (16, TOPK_R)), _sds(s, (16, TOPK_R))),
        lambda: TK.why_not(16, TOPK_R, TOPK_K)),
    "topk_ef.compact": (
        lambda lines, counts, t, last: TKC.ef_compact(
            lines, counts, t, last, k=EMBED_R // 16),
        lambda s: (_sds(s, (EMBED_R // 128, 128)),
                   _sds(s, (EMBED_R // 128,), jnp.int32),
                   _sds(s, (), jnp.int32), _sds(s, (), jnp.int32)),
        lambda: TK.compact_why_not(EMBED_R)),
    "onebit_ef": (
        onebit_ef,
        lambda s: (_sds(s, (16, ONEBIT_R)), _sds(s, (16, ONEBIT_R))),
        lambda: OB.why_not(16, ONEBIT_R)),
    "cr_reduce.onebit_reduce": (
        CRK.onebit_cr_reduce,
        lambda s: (_sds(s, (CR_S, 16, CR_R), jnp.bool_),
                   _sds(s, (CR_S, 16, 2)), _sds(s, (CR_S,))),
        lambda: CR._why_not(16, 8, {"S": CR_S, "R": CR_R}) or
        dispatch.vmem_why_not(CR.onebit_reduce_vmem_bytes(CR_S, 8, CR_R),
                              "panel")),
    "swa_attention": (
        lambda q, k, v, p, b: swa_decode_attention(q, k, v, p, b,
                                                   window=4096),
        lambda s: (_sds(s, (8, 8, 2, 128), jnp.bfloat16),
                   _sds(s, (8, 8192, 8, 128), jnp.bfloat16),
                   _sds(s, (8, 8192, 8, 128), jnp.bfloat16),
                   _sds(s, (8,), jnp.int32), _sds(s, (8,), jnp.int32)),
        lambda: SWA.why_not(8192)),
    "sim_step.delivery_step": (
        lambda v, x, a, xs, n, u, d: SSK.delivery_step(
            v, x, a, xs, n, u, d, has_defer=True),
        lambda s: (_sds(s, (SIM_P, SIM_D)), _sds(s, (1, SIM_D)),
                   _sds(s, (SIM_D, SIM_D)), _sds(s, (1, SIM_D)),
                   _sds(s, (SIM_P, SIM_D)), _sds(s, (1 + 2 * SIM_P, SIM_P)),
                   _sds(s, (SIM_P, SIM_D))),
        lambda: SSO.why_not(SIM_P, SIM_D, 1 + 2 * SIM_P, 256)),
    "sim_step.sync_step": (
        SSK.sync_step,
        lambda s: (_sds(s, (1, SIM_D)), _sds(s, (SIM_D, SIM_D)),
                   _sds(s, (1, SIM_D)), _sds(s, (1, SIM_D)),
                   _sds(s, (1, 1))),
        lambda: SSO.why_not(1, SIM_D, 1, 256)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, on_tpu):
    fn, shapes, why_not = KERNELS[name]
    assert why_not() is None, why_not()       # the TPU dispatch takes it
    compiled = jax.jit(fn).lower(*shapes(one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


ROUTED = {
    "cr_reduce.topk_reduce": lambda: CR.topk_reduce(
        jnp.ones((2, 8, 4)), jnp.zeros((2, 8, 4), jnp.int32), jnp.ones(2),
        128, impl="kernel"),
    "cr_reduce.topk_deposit": lambda: CR.topk_deposit(
        jnp.zeros((2, 8, 128)), jnp.ones((2, 8, 4)),
        jnp.zeros((2, 8, 4), jnp.int32), jnp.zeros(2, jnp.int32),
        jnp.ones(2), impl="kernel"),
    "cr_reduce.onebit_deposit": lambda: CR.onebit_deposit(
        jnp.zeros((2, 8, 128)), jnp.ones((2, 8, 128), bool),
        jnp.ones((2, 8, 2)), jnp.zeros(2, jnp.int32), jnp.ones(2),
        impl="kernel"),
}


@pytest.mark.parametrize("site", sorted(ROUTED))
def test_routed_kernels_raise_on_tpu(site, on_tpu):
    """No scatter-add in Mosaic: these resolve to jnp on TPU and refuse
    ``impl="kernel"`` instead of falling back."""
    with pytest.raises(dispatch.KernelUnsupported,
                       match=CR.NO_SCATTER_ADD):
        jax.eval_shape(ROUTED[site])


@pytest.mark.parametrize("m,r", [(1, EMBED_R), (8, 1 << 20)])
def test_topk_ef_whole_leaf_raises(m, r, on_tpu):
    """The tied embedding as one row (M = 1) and a row block over the VMEM
    budget are refused under ``impl="kernel"``, resolved to the reference
    under ``"auto"``: the threshold search, whose compaction is the
    streaming kernel."""
    g = jax.ShapeDtypeStruct((m, r), jnp.float32)
    with pytest.raises(dispatch.KernelUnsupported):
        jax.eval_shape(lambda a, b: TK.compress_leaf(a, b, 1 / 16,
                                                     impl="kernel"), g, g)
    dispatch.reset_resolutions()
    jax.eval_shape(lambda a, b: TK.compress_leaf(a, b, 1 / 16), g, g)
    (how,) = dispatch.resolutions()[TK.SITE]
    assert how.startswith("ref (")
    assert dispatch.resolutions()[TK.COMPACT_SITE] == {"kernel": 1}


def test_ef_residual_skips_the_compaction(one_chip, on_tpu):
    """The EF rounds that keep only the residual (``compression``'s
    batched rows) compile no compaction kernel for a long row: the jnp
    compaction they keep is dead code to XLA."""
    comp = COMP.topk_compressor(1 / 16)
    rows = _sds(one_chip, (4, 1 << 20))
    dispatch.reset_resolutions()
    compiled = jax.jit(lambda u, e: COMP.ef_compress_rows(comp, u, e)).lower(
        rows, rows).compile()
    assert TK.COMPACT_SITE not in dispatch.resolutions()
    assert "tpu_custom_call" not in compiled.as_text()


def test_auto_picks_compiled_kernel_on_tpu(on_tpu):
    dispatch.reset_resolutions()
    g = jax.ShapeDtypeStruct((16, TOPK_R), jnp.float32)
    jax.eval_shape(lambda a, b: TK.compress_leaf(a, b, 1 / 16), g, g)
    assert dispatch.resolutions()[TK.SITE] == {"kernel": 1}
