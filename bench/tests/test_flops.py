"""bench/flops.py against hand-worked counts, and against the parameter
trees the trainer builds."""
import dataclasses
import math

import pytest

from bench import flops
from bench import manifest as M


def _conf(name):
    return M.config(M.load(), name)


def test_qwen3_4_layers_by_hand():
    c = _conf("qwen3-1.7b")
    # per layer: wq + wo 2 x 2048 x 2048, wk + wv 2 x 2048 x 1024,
    # MLP 3 x 2048 x 6144; head (tied) 151936 x 2048
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    assert layer == 50_331_648
    assert flops.qwen3_matmul_params(c) == 4 * layer + 311_164_928 \
        == 512_491_520
    attn = 6 * 4 * 16 * 128 * 513
    assert flops.train_flops_per_token(c, 512) == \
        6 * 512_491_520 + attn == 3_100_164_096
    # about 3.08 GFLOP a token from the matmuls, attention under 1%
    assert attn / (6 * 512_491_520) < 0.01


def test_rwkv6_4_layers_by_hand():
    c = _conf("rwkv6-1.6b")
    layer = 7 * 2048 ** 2 + 2 * 2048 * 7168
    assert flops.rwkv6_matmul_params(c) == 4 * layer + 65536 * 2048 \
        == 369_098_752
    wkv = 12 * 32 * 64 * 64 * 4
    assert flops.train_flops_per_token(c, 512) == \
        6 * 369_098_752 + wkv == 2_220_883_968


@pytest.mark.parametrize("name", ["qwen3-1.7b", "qwen3-1.7b-l1",
                                  "rwkv6-1.6b"])
def test_matmul_params_match_the_trainers_matrices(name):
    """Every parameter the count takes is a matrix of the trainer's tree,
    and every matrix of the tree is taken: a tied embedding once, as the
    head; an untied one not at all (a gather)."""
    from bench.drivers.train import program_config
    from repro.models import transformer as TF
    from repro.models.params import abstract_params
    conf = _conf(name)
    cfg = program_config(conf)
    shapes = abstract_params(TF.model_defs(cfg))
    import jax
    mats = 0
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        stacked = getattr(path[0], "key", None) == "layers"
        per_layer = math.prod(s.shape[1:] if stacked else s.shape)
        gather = path[0].key == "embed" and "lm_head" in shapes
        if per_layer >= 1 << 20 and not gather:  # norms, mixes: smaller
            mats += math.prod(s.shape)
    count = getattr(flops, conf["flops"] + "_matmul_params")(conf)
    assert count == mats


def test_mfu_reader_and_peak_table():
    peaks = M.read_json(M.ROOT / "bench" / "peaks.json")
    assert peaks["kinds"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    mfu = M.load_module(M.metric_path("mfu"))
    r = {"red": {"window_s": 1.0}, "steps": 10, "chips": 1,
         "peak": peaks["kinds"]["TPU v5 lite"], "flops_per_step": 6.4e12}
    assert mfu.read(r) == pytest.approx(100 * 64e12 / 197e12)
    assert mfu.read(dict(r, steps=0)) is None


def test_program_config_takes_the_files_values():
    from bench.drivers.train import program_config
    cfg = program_config(_conf("qwen3-1.7b"))
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.tie_embeddings) == \
        (4, 2048, 16, 8, 128, 6144, 151936, True)
    r = program_config(_conf("rwkv6-1.6b"))
    assert (r.n_layers, r.d_model, r.ssm_heads * r.ssm_state, r.d_ff,
            r.vocab_size, r.tie_embeddings, r.norm_eps) == \
        (4, 2048, 2048, 7168, 65536, False, 1e-5)
    assert dataclasses.replace(cfg, n_layers=28).n_layers == 28
