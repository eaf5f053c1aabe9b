"""Model FLOPs of one training token of the Moonlight cell (DeepSeek-V3
block: latent attention, a leading dense layer, then MoE layers of routed
and shared experts), from its file's published shapes.

As in ``bench/flops.py``: three passes of the forward products, two FLOPs
a multiply-add, nothing recomputed, no elementwise work; the embedding is
a gather, the untied head a product. The routed experts count at their
expected share of a token: ``num_experts_per_tok`` of the
``n_routed_experts_published`` router outputs, times the
``n_routed_experts`` held here, so that the count does not depend on how
the program computes them.
"""
from __future__ import annotations


def mla_params(conf: dict) -> int:
    """q, the kv down-projection (latent and rope key), the kv
    up-projection (nope keys and values) and the output projection."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    nope, rope, v = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                     conf["v_head_dim"])
    r = conf["kv_lora_rank"]
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d


def moe_params(conf: dict) -> float:
    """Per token: the shared experts, the router, and the held experts at
    their expected share."""
    d, ff = conf["hidden_size"], conf["moe_intermediate_size"]
    share = conf["num_experts_per_tok"] * conf["n_routed_experts"] / \
        conf["n_routed_experts_published"]
    return 3 * d * ff * conf["n_shared_experts"] \
        + d * conf["n_routed_experts_published"] + share * 3 * d * ff


def matmul_params(conf: dict) -> float:
    dense = conf["first_k_dense_replace"]
    moe = conf["num_hidden_layers"] - dense
    return (conf["num_hidden_layers"] * mla_params(conf)
            + dense * 3 * conf["hidden_size"] * conf["intermediate_size"]
            + moe * moe_params(conf)
            + conf["vocab_size"] * conf["hidden_size"])


def train_flops_per_token(conf: dict, seq: int) -> float:
    """6 N_matmul plus causal attention: per layer and token, q.k over the
    (nope + rope) width and p.v over the v width, (seq + 1) / 2 keys on
    average, three passes: 3 L H (nope + rope + v) (seq + 1)."""
    width = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"] + \
        conf["v_head_dim"]
    attn = 3 * conf["num_hidden_layers"] * conf["num_attention_heads"] * \
        width * (seq + 1)
    return 6.0 * matmul_params(conf) + attn


#: grouped products a MoE layer's training step runs per row of the held
#: experts, each 2 d ff FLOPs: the forward's gate, up and down; gate and
#: up again in the backward, where the routed part is recomputed (the
#: down product's output is not needed there); and each product's input
#: and weight gradients
GMM_PRODUCTS = 3 + 2 + 2 * 3


def held_rows(conf: dict, tokens: int) -> float:
    """Assignments to the held experts a MoE layer sees at the expected
    load: tokens x k x held / published."""
    return tokens * conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / conf["n_routed_experts_published"]


def _moe_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def gmm_flops_per_step(conf: dict, tokens: int) -> float:
    """FLOPs of the held experts' grouped products in a step of
    ``tokens`` at the expected load."""
    d, ff = conf["hidden_size"], conf["moe_intermediate_size"]
    return _moe_layers(conf) * GMM_PRODUCTS * 2 * d * ff \
        * held_rows(conf, tokens)


def gmm_bytes_per_step(conf: dict, tokens: int, itemsize: int = 2) -> float:
    """The least HBM traffic of those products: each reads its rows and
    the held experts' weights, and writes its output, once (bf16)."""
    d, ff = conf["hidden_size"], conf["moe_intermediate_size"]
    per_product = held_rows(conf, tokens) * (d + ff) \
        + conf["n_routed_experts"] * d * ff
    return _moe_layers(conf) * GMM_PRODUCTS * itemsize * per_product
