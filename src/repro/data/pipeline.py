"""Deterministic synthetic data pipeline.

No datasets ship in this container, so the pipeline generates *learnable*
synthetic token streams: a fixed random Markov-chain over the vocabulary
(temperature-controlled), so the loss has real signal (a model that learns
the transition table beats the entropy floor) and convergence benchmarks are
meaningful. Batches are a pure function of (seed, step) — restart-safe and
shardable (each data shard derives its slice from its global batch offset,
so the global stream is independent of the mesh layout).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, InputShape


@dataclass
class SyntheticLMDataset:
    """Markov-chain token stream. ``batch(step)`` -> dict of arrays."""

    vocab_size: int
    seq_len: int
    batch_size: int
    seed: int = 0
    order_states: int = 64  # markov states (vocab folded into states)

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        s = self.order_states
        logits = rng.normal(size=(s, s)) * 2.0
        self._trans = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        # deterministic state->token expansion
        self._emit = rng.integers(0, self.vocab_size, size=(s, 4))

    @partial(jax.profiler.annotate_function, name="pipeline.batch")
    def batch(self, step: int) -> dict:
        """Host spans: ``pipeline.batch`` around the call,
        ``pipeline.markov`` around the per-position sampling loop."""
        rng = np.random.default_rng((self.seed, step))
        b, t, s = self.batch_size, self.seq_len, self.order_states
        states = np.zeros((b, t + 1), np.int64)
        states[:, 0] = rng.integers(0, s, size=b)
        u = rng.random((b, t))
        cdf = np.cumsum(self._trans, axis=-1)
        with jax.profiler.TraceAnnotation("pipeline.markov"):
            for i in range(t):
                states[:, i + 1] = np.argmax(
                    cdf[states[:, i]] > u[:, i:i + 1], axis=-1)
        emit_choice = rng.integers(0, self._emit.shape[1], size=(b, t + 1))
        tokens = self._emit[states, emit_choice].astype(np.int32)
        return {
            "tokens": jnp.asarray(tokens[:, :-1]),
            "labels": jnp.asarray(tokens[:, 1:]),
        }


def synthetic_batch(cfg: ArchConfig, batch_size: int, seq_len: int,
                    seed: int = 0) -> dict:
    """One random batch with the frontend-stub extras an arch needs."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(k1, (batch_size, seq_len), 0,
                                     cfg.vocab_size, dtype=jnp.int32),
        "labels": jax.random.randint(k2, (batch_size, seq_len), 0,
                                     cfg.vocab_size, dtype=jnp.int32),
    }
    if cfg.frontend == "audio":
        batch["frame_embeds"] = 0.02 * jax.random.normal(
            k3, (batch_size, seq_len, cfg.d_model), jnp.float32)
    if cfg.frontend == "vision":
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            k3, (batch_size, cfg.n_prefix_embeds, cfg.d_model), jnp.float32)
    return batch


def make_batch_specs(cfg: ArchConfig, shape: InputShape) -> dict:
    """Allocation-free ShapeDtypeStruct stand-ins for every model input of a
    workload (the dry-run's ``input_specs()``)."""
    b = shape.global_batch
    if shape.kind in ("train", "prefill"):
        s = shape.seq_len
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if cfg.frontend == "audio":
            specs["frame_embeds"] = jax.ShapeDtypeStruct(
                (b, s, cfg.d_model), jnp.float32)
        if cfg.frontend == "vision":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_prefix_embeds, cfg.d_model), jnp.float32)
        return specs
    # decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
