"""The token stream the trainer's data pipeline promises, generated here
on its own so the reference never reads a batch the program made.

A Markov chain over ``states`` hidden states with Gaussian(0, 2) transition
logits, each state emitting one of four fixed tokens; the chain table and
the emission table come from ``default_rng(seed)``, and the batch of step
``t`` from ``default_rng((seed, t))``: first the start states, then one
uniform draw per position, then the emission choices. Rows are tokens
``[0, S)`` and labels ``[1, S]`` of an ``S + 1``-long draw.
"""
from __future__ import annotations

import numpy as np


def tables(vocab: int, seed: int, states: int = 64):
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(states, states)) * 2.0
    trans = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    emit = rng.integers(0, vocab, size=(states, 4))
    return np.cumsum(trans, axis=-1), emit


def batch(vocab: int, seq: int, rows: int, seed: int, step: int,
          states: int = 64, _tables=None):
    """(tokens, labels), each (rows, seq) int32."""
    cdf, emit = _tables or tables(vocab, seed, states)
    rng = np.random.default_rng((seed, step))
    s = np.zeros((rows, seq + 1), np.int64)
    s[:, 0] = rng.integers(0, states, size=rows)
    u = rng.random((rows, seq))
    # inverse-CDF draw: the first state whose cumulative probability
    # exceeds u; where rounding leaves none (u above the last entry) the
    # pipeline takes state 0
    for i in range(seq):
        n = (cdf[s[:, i]] <= u[:, i:i + 1]).sum(-1)
        s[:, i + 1] = np.where(n == states, 0, n)
    choice = rng.integers(0, emit.shape[1], size=(rows, seq + 1))
    toks = emit[s, choice].astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
