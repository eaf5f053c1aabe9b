"""The training step's named device scopes and the host spans around it.

A CPU run has no device time, so the scopes are checked where they live:
in the ``op_name`` metadata of the compiled step's HLO, which is what
``bench/scopes.py`` maps the chip's trace through. The host spans are
checked in a profiler trace that ``train()`` writes on the CPU.
"""
import numpy as np
import pytest

from bench import scopes as S
from bench.reference import markov

BASE = {"model_fwd_bwd", "lm_head", "optimizer"}


def _compiled_scopes(arch: str, sync: str, overlap: bool = True) -> list:
    """Each instruction's scopes in the compiled step of ``arch``'s smoke
    size on one device, exact or async top-k with error feedback."""
    import jax

    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLMDataset
    from repro.dist import sharding as SH
    from repro.dist.async_engine import (AsyncConfig, init_async_state,
                                         make_async_train_step)
    from repro.dist.train import make_train_step
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as TF
    from repro.models.params import init_params, param_specs
    from repro.optim import momentum

    cfg = get_config(arch).reduced()
    mesh = make_host_mesh(devices=jax.devices()[:1])
    flags = TF.RunFlags(remat=False)
    pspecs = param_specs(TF.model_defs(cfg), SH.axis_sizes(mesh))
    params = init_params(TF.model_defs(cfg), jax.random.PRNGKey(0))
    opt = momentum(1e-2, 0.9)
    batch = SyntheticLMDataset(cfg.vocab_size, 16, 2).batch(0)
    if sync == "exact":
        step = make_train_step(cfg, opt, flags)
        args = (params, opt.init(params), batch)
    else:
        acfg = AsyncConfig(tau_max=1, compressor="topk", topk_ratio=1 / 16,
                           error_feedback=True, overlap=overlap, horizon=8)
        step = make_async_train_step(cfg, opt, mesh, acfg, pspecs, flags)
        args = (params, opt.init(params),
                init_async_state(acfg, mesh, params,
                                 pspecs if acfg.fused else None), batch)
    text = jax.jit(step).lower(*args).compile().as_text()
    return [S.scopes_of(n) for n in S.op_names(text).values()]


@pytest.mark.parametrize("arch,sync,overlap,want", [
    ("qwen3-1.7b", "exact", True, BASE),
    ("rwkv6-1.6b", "exact", True, BASE | {"wkv"}),
    ("qwen3-1.7b", "async", True, BASE | {"compress", "deliver"}),
    ("qwen3-1.7b", "async", False, BASE | {"compress", "deliver"}),
], ids=["qwen3-exact", "rwkv6-exact", "qwen3-async-fused",
        "qwen3-async-densified"])
def test_step_carries_its_scopes(arch, sync, overlap, want):
    found = _compiled_scopes(arch, sync, overlap)
    assert set().union(*found) == want
    # the head is part of the differentiated loss, forward and backward;
    # the update and the wire are not
    assert any({"model_fwd_bwd", "lm_head"} <= f for f in found)
    for outside in ("optimizer", "compress", "deliver"):
        assert not any({"model_fwd_bwd", outside} <= f for f in found)
    if "wkv" in want:
        assert all("model_fwd_bwd" in f for f in found if "wkv" in f)


def test_scope_components():
    name = "jit(step)/model_fwd_bwd/transpose(jvp(lm_head))/jit(f)/mul"
    assert S.scopes_of(name) == {"model_fwd_bwd", "lm_head"}
    assert S.scopes_of("jit(step)/optimizer/add") == {"optimizer"}
    assert S.scopes_of("jit(step)/lm_head_x/wkvs/add") == set()
    assert S.scopes_of("") == set()
    hlo = """
  %fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, calls=%c, metadata={op_name="jit(s)/jvp(wkv)/mul" source_file="a.py" source_line=3}
  ROOT %while.7 = (s32[]) while((s32[]) %t), condition=%cond, body=%body, metadata={op_name="jit(s)/optimizer/while"}
  %copy-done = f32[2]{0} copy-done(f32[2]{0} %cs)
"""
    assert S.op_names(hlo) == {"fusion.3": "jit(s)/jvp(wkv)/mul",
                               "while.7": "jit(s)/optimizer/while",
                               "copy-done": ""}


@pytest.mark.parametrize("seed,step", [(0, 0), (7, 3), (2**31 + 5, 11)])
def test_annotated_batch_is_the_reference_stream(seed, step):
    from repro.data.pipeline import SyntheticLMDataset
    data = SyntheticLMDataset(997, 24, 3, seed=seed)
    got = data.batch(step)
    tokens, labels = markov.batch(997, 24, 3, seed, step)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), tokens)
    np.testing.assert_array_equal(np.asarray(got["labels"]), labels)


def test_train_writes_a_profile_of_the_chosen_steps(tmp_path, monkeypatch):
    import jax
    from jax.profiler import ProfileData

    from bench import trace_reduce as T
    from repro.configs import get_config
    from repro.launch import mesh as launch_mesh
    from repro.launch.train import parse_args, train

    make = launch_mesh.make_host_mesh
    monkeypatch.setattr(launch_mesh, "make_host_mesh",
                        lambda **kw: make(**{"devices": jax.devices()[:1],
                                             **kw}))
    out = tmp_path / "prof"
    args = parse_args(["--steps", "4", "--batch", "2", "--seq", "16",
                       "--log-every", "100", "--profile-dir", str(out),
                       "--profile-steps", "1:3"])
    run = train(args, get_config("qwen3-1.7b").reduced())
    assert len(run.losses) == 4
    pd = ProfileData.from_file(str(T.find_trace(out)))
    events = [e for p in pd.planes if p.name == T.HOST_PLANE
              for line in p.lines for e in line.events]
    names = [e.name for e in events]
    steps = sorted(dict(e.stats)["step_num"] for e in events
                   if e.name == "train_step")
    assert steps == [1, 2]
    for span in ("pipeline.batch", "pipeline.markov", "batch.device_put",
                 "step.call", "loss.fetch"):
        assert names.count(span) == 2, span
    found = set().union(*(S.scopes_of(n) for n in S.op_names(
        (out / "step.hlo.txt").read_text()).values()))
    assert found == BASE


@pytest.mark.parametrize("argv", [
    ["--profile-dir", "x"], ["--profile-steps", "0:2"],
    ["--profile-dir", "x", "--profile-steps", "3:3"],
    ["--profile-dir", "x", "--profile-steps", "2"],
])
def test_profile_flags_are_checked(argv):
    from repro.launch.train import parse_args
    with pytest.raises(SystemExit):
        parse_args(argv)
