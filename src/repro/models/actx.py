"""Activation-sharding context.

Model code calls ``constrain(x, kind)`` at the canonical Megatron constraint
points; the launcher installs a rule set (kind -> NamedSharding) before
tracing. Without rules every call is a no-op, so CPU smoke tests and the
simulator never touch mesh state. Keeping the rules out of the model
signature lets the same forward serve pjit, shard_map (where batch axes must
be dropped from the specs) and single-host execution.

Kinds:
  residual     (B, S, d)        — between blocks (sequence parallelism)
  ffn_hidden   (B, S, ff)       — MLP hidden, model on ff
  attn_q       (B, S, H, hd)    — projected q / attention output
  attn_kv      (B, S, K, hd)    — projected k/v
  logits       (B, S, V)        — LM head output, model on V
"""
from __future__ import annotations

import contextlib

import jax

_RULES: dict = {}


def set_rules(rules: dict) -> None:
    global _RULES
    _RULES = dict(rules or {})


def get_rules() -> dict:
    return dict(_RULES)


@contextlib.contextmanager
def rules(r: dict):
    old = get_rules()
    set_rules(r)
    try:
        yield
    finally:
        set_rules(old)


def constrain(x, kind: str):
    s = _RULES.get(kind)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)
