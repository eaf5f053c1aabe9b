"""What the first steps of data-parallel momentum SGD should do, followed
in float32 from the same weights and tokens.

Each of ``n`` workers holds ``rows / n`` rows of the step's batch and its
gradient is the mean over its rows. Then, by the traffic's ``sync``:

  exact   the update is the mean of the workers' gradients;
  async   worker w's message of step s is, with ``compressor: topk``,
          the ``k = max(1, round(size * topk_ratio))`` largest-magnitude
          entries of each leaf of ``e + g`` (with ``ef``, ``e`` keeps the
          rest, else ``e = 0``); it is applied at step s + tau(s, w), with
          tau drawn iid uniform on {0..tau_max} by ``default_rng(seed)``
          as a (horizon, n) table; the update of step t is the sum of the
          messages that arrive at t, over n.

and momentum SGD: ``mu = beta mu + update``, ``p = p - lr mu``.

The mean gradient is taken in blocks of rows, and the compression leaf by
leaf, so that the reference fits beside nothing else on one chip.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench.reference import common as C


def tau_table(step: dict, seed: int, workers: int) -> np.ndarray:
    if step["sync"] != "async":
        return np.zeros((1, workers), np.int64)
    if step["schedule"] != "uniform":
        raise ValueError(f"no reference for schedule {step['schedule']!r}")
    rng = np.random.default_rng(seed)
    return rng.integers(0, step["tau_max"] + 1,
                        size=(step["horizon"], workers))


def first_update_step(step: dict, seed: int, workers: int) -> int:
    """The first step whose update is not empty."""
    taus = tau_table(step, seed, workers)
    return int(min(s + taus[s, w] for s in range(taus.max() + 1)
                   for w in range(workers)))


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


_add_jit = jax.jit(_add, donate_argnums=0)


def _scale_impl(tree, c):
    return jax.tree.map(lambda a: a * c, tree)


_scale = jax.jit(_scale_impl, donate_argnums=0)


def mean_grad(lossgrad, params, tokens, labels, block: int):
    """Mean loss and gradient over the rows of (tokens, labels)."""
    rows = tokens.shape[0]
    assert rows % block == 0, (rows, block)
    loss, grad = 0.0, None
    for i in range(0, rows, block):
        lv, g = lossgrad(params, tokens[i:i + block], labels[i:i + block])
        loss += float(lv)
        grad = g if grad is None else _add_jit(grad, g)
        del g
    nb = rows // block
    return loss / nb, _scale(grad, 1.0 / nb)


def _momentum_impl(mu, upd, params, beta, lr):
    mu = jax.tree.map(lambda m, u: beta * m + u, mu, upd)
    return mu, jax.tree.map(lambda p, m: p - lr * m, params, mu)


_momentum = jax.jit(_momentum_impl, donate_argnums=(0, 2))


def _topk_leaf(w, k: int, ef: bool):
    flat = w.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    q = jnp.zeros_like(flat).at[idx].set(flat[idx]).reshape(w.shape)
    return q, (w - q) if ef else jnp.zeros_like(w)


_topk_jit = jax.jit(_topk_leaf, static_argnums=(1, 2), donate_argnums=0)


def _compress(step, err, grad):
    """Per leaf: (message, new residual) from residual + gradient."""
    if step["compressor"] != "topk":
        raise ValueError(f"no reference for {step['compressor']!r}")
    qs, es = [], []
    for e, g in zip(jax.tree.leaves(err), jax.tree.leaves(grad)):
        k = max(1, int(round(g.size * step["topk_ratio"])))
        q, e2 = _topk_jit(e + g, k, bool(step["ef"]))
        qs.append(q)
        es.append(e2)
    tdef = jax.tree.structure(grad)
    return jax.tree.unflatten(tdef, qs), jax.tree.unflatten(tdef, es)


def follow(model, conf: dict, step: dict, init, key, batches, seed: int,
           workers: int = 1, rnd=C.EXACT, block: int = 1,
           fault: str | None = None) -> dict:
    """Follow ``len(batches)`` steps from ``init(key)``.

    Returns per-step ``losses`` and per-leaf norms: ``grad0`` (the first
    mean gradient), ``first`` (momentum after :func:`first_update_step`,
    which is then the first update itself; ``first_host`` holds its
    leaves on the host) and ``change`` (parameters
    after the last step minus the initial ones). ``fault="half_batch"``
    drops the second half of every worker's rows.
    """
    lossgrad = jax.jit(jax.value_and_grad(
        lambda p, t, l: model.logits_and_loss(p, t, l, conf, rnd)))
    params = init(key)
    mu = jax.tree.map(jnp.zeros_like, params)
    taus = tau_table(step, seed, workers)
    first_at = first_update_step(step, seed, workers)
    err = [jax.tree.map(jnp.zeros_like, params) for _ in range(workers)] \
        if step["sync"] == "async" else None
    cap = step.get("tau_max", 0) + 1
    ring: dict = {}
    out = {"losses": []}
    per = batches[0][0].shape[0] // workers
    for t, (tokens, labels) in enumerate(batches):
        upd, losses = None, []
        for w in range(workers):
            lo = w * per
            hi = lo + (per // 2 if fault == "half_batch" else per)
            loss, g = mean_grad(lossgrad, params, tokens[lo:hi],
                                labels[lo:hi], min(block, hi - lo))
            losses.append(loss)
            if t == 0 and w == 0:
                out["grad0"] = [float(x) for x in C.leaf_norms(g)]
            if step["sync"] == "async":
                msg, err[w] = _compress(step, err[w], g)
                slot = (t + int(taus[t % len(taus), w])) % cap
                ring[slot] = msg if slot not in ring else _add_jit(
                    ring[slot], msg)
                del msg
            else:
                upd = g if upd is None else _add_jit(upd, g)
            del g
        if step["sync"] == "async":
            upd = ring.pop(t % cap, None)
            if upd is None:
                upd = jax.tree.map(jnp.zeros_like, params)
        upd = _scale(upd, 1.0 / workers)
        out["losses"].append(sum(losses) / workers)
        mu, params = _momentum(mu, upd, params, step["momentum"],
                               step["lr"])
        del upd
        if t == first_at:
            out["first"] = [float(x) for x in C.leaf_norms(mu)]
            out["first_host"] = C.host_leaves(mu)
    del mu, err, ring
    p0 = init(key)
    out["change"] = [float(x) for x in C.diff_norms(params, p0)]
    return out
