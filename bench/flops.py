"""Model FLOPs of one training token, from a configuration's published
shapes (the keys of its file under bench/configs).

Training counts three passes of the forward products (forward, and the
two products of the backward), two FLOPs per multiply-add; recomputed
products are not counted, nor elementwise work, norms or the softmax.
The embedding lookup is a gather, not a product; a tied embedding counts
once, as the head.

A configuration names its count by ``flops`` in its file; a count that is
not here lives in ``bench/flops_<name>.py`` as ``train_flops_per_token``.
"""
from __future__ import annotations

from pathlib import Path


def qwen3_matmul_params(conf: dict) -> int:
    """Parameters that enter a matrix product once per token."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    h, kv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff
    return conf["num_hidden_layers"] * layer + conf["vocab_size"] * d


def qwen3(conf: dict, seq: int) -> float:
    """6 N_matmul plus causal attention: per layer and token, q.k and
    p.v over (seq + 1) / 2 keys on average, 2 x 2 x heads x head_dim FLOPs
    per key, three passes: 6 L H hd (seq + 1)."""
    attn = 6 * conf["num_hidden_layers"] * conf["num_attention_heads"] * \
        conf["head_dim"] * (seq + 1)
    return 6.0 * qwen3_matmul_params(conf) + attn


def rwkv6_matmul_params(conf: dict) -> int:
    """Per layer seven d x d projections (r, k, v, g, decay, output,
    channel-mix receptance) and the two channel-mix matrices; plus the
    untied head."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    layer = 7 * d * d + 2 * d * ff
    return conf["num_hidden_layers"] * layer + conf["vocab_size"] * d


def rwkv6(conf: dict, seq: int) -> float:
    """6 N_matmul plus the WKV recurrence: per head and token the read-out
    r.(S + u k v^T) and the update diag(w) S + k v^T, 4 N^2 FLOPs forward,
    three passes: 12 H N^2 per layer."""
    d, n = conf["hidden_size"], conf["head_size"]
    wkv = 12 * (d // n) * n * n * conf["num_hidden_layers"]
    return 6.0 * rwkv6_matmul_params(conf) + wkv


def train_flops_per_token(conf: dict, seq: int) -> float:
    name = conf["flops"]
    fn = {"qwen3": qwen3, "rwkv6": rwkv6}.get(name)
    if fn is None:
        from bench import manifest
        fn = manifest.load_module(
            Path(__file__).with_name(f"flops_{name}.py")
        ).train_flops_per_token
    return float(fn(conf, seq))
