"""RWKV-6 (Finch) block — attention-free, data-dependent per-channel decay.

Time-mixing recurrence per head (k/v dim N):

    S_t = diag(w_t) S_{t-1} + k_t (x) v_t        w_t = exp(-exp(w_raw(x_t)))
    o_t = r_t . (S_{t-1} + diag(u) k_t (x) v_t)

Chunked evaluation (chunk C) mirrors Mamba2's SSD but with *vector* decays:
the intra-chunk kernel is L[t,j,i] = exp(lw_t[i] - lw_j[i]) for j < t, which
is computed as a (C, C, N) tensor per (batch, head) — numerically safe since
lw is a running sum of negative log-decays (t >= j => exponent <= 0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import ParamDef
from repro.models.layers import rmsnorm

CHUNK = 64
MIX_KEYS = ("r", "k", "v", "w", "g")


def rwkv6_defs(cfg) -> dict:
    d = cfg.d_model
    h, n = cfg.ssm_heads, cfg.ssm_state
    assert h * n == d, (h, n, d)
    defs = {
        "ln1": ParamDef((d,), ("embed",), init="ones"),
        "ln2": ParamDef((d,), ("embed",), init="ones"),
        "mix": ParamDef((len(MIX_KEYS), d), (None, "embed"), init="zeros"),
        "w_r": ParamDef((d, d), ("embed", "dinner")),
        "w_k": ParamDef((d, d), ("embed", "dinner")),
        "w_v": ParamDef((d, d), ("embed", "dinner")),
        "w_g": ParamDef((d, d), ("embed", "dinner")),
        # data-dependent decay projection (low-rank in the release; dense here)
        "w_decay": ParamDef((d, d), ("embed", "dinner"), scale=0.01),
        "decay_bias": ParamDef((d,), ("embed",), init="constant", constant=-4.0),
        "bonus_u": ParamDef((h, n), (None, None), init="zeros"),
        "ln_x": ParamDef((d,), ("embed",), init="ones"),
        "w_o": ParamDef((d, d), ("dinner", "embed")),
        # channel-mix
        "cm_mix": ParamDef((2, d), (None, "embed"), init="zeros"),
        "cm_k": ParamDef((d, cfg.d_ff), ("embed", "ff")),
        "cm_v": ParamDef((cfg.d_ff, d), ("ff", "embed")),
        "cm_r": ParamDef((d, d), ("embed", "dinner")),
    }
    return defs


def _token_shift(x, last):
    """x: (B,T,d); last: (B,1,d) previous segment's final token (or zeros)."""
    return jnp.concatenate([last.astype(x.dtype), x[:, :-1]], axis=1)


def wkv6_chunked(r, k, v, log_w, u, state0=None):
    """r/k/v: (B,T,H,N); log_w: (B,T,H,N) (<0); u: (H,N).
    Returns (out (B,T,H,N), final_state (B,H,N,N))."""
    b, t, h, n = r.shape
    c = min(CHUNK, t)
    assert t % c == 0, (t, c)
    nc = t // c
    rs, ks, vs, ws = (a.reshape(b, nc, c, h, n).swapaxes(0, 1)
                      for a in (r, k, v, log_w))
    if state0 is None:
        state0 = jnp.zeros((b, h, n, n), jnp.float32)

    def step(state, inp):
        rc, kc, vc, wc = inp                      # (b,c,h,n)
        lw = jnp.cumsum(wc.astype(jnp.float32), axis=1)  # inclusive
        lw_excl = lw - wc.astype(jnp.float32)            # exclusive
        # intra-chunk, strictly causal (j < t): o_t sees S_{t-1}, so k_j is
        # decayed by prod_{s=j+1..t-1} w_s = exp(lw_excl_t - lw_j)
        ldiff = lw_excl[:, :, None] - lw[:, None, :, :, :]  # (b,c,c,h,n)
        mask = jnp.tril(jnp.ones((c, c), bool), k=-1)
        lmat = jnp.where(mask[None, :, :, None, None], jnp.exp(ldiff), 0.0)
        amat = jnp.einsum("bthn,btjhn,bjhn->bthj",
                          rc.astype(jnp.float32), lmat,
                          kc.astype(jnp.float32))
        # diagonal bonus term
        diag = jnp.einsum("bthn,hn,bthn->bth", rc.astype(jnp.float32), u,
                          kc.astype(jnp.float32))
        y = jnp.einsum("bthj,bjhn->bthn", amat, vc.astype(jnp.float32))
        y = y + diag[..., None] * vc.astype(jnp.float32)
        # inter-chunk: S_prev seen by step t after decaying through 1..t-1
        y = y + jnp.einsum("bthn,bhnm->bthm",
                           rc.astype(jnp.float32) * jnp.exp(lw_excl), state)
        # state update: S_new = diag(w_total) S + sum_j decay(j+1..C) k_j (x) v_j
        decay_rest = jnp.exp(lw[:, -1:] - lw)              # (b,c,h,n)
        ktil = kc.astype(jnp.float32) * decay_rest
        new_state = jnp.exp(lw[:, -1])[..., None] * state + \
            jnp.einsum("bchn,bchm->bhnm", ktil, vc.astype(jnp.float32))
        return new_state, y.astype(rc.dtype)

    from repro.models.scan_utils import scan as _scan
    final, ys = _scan(step, state0, (rs, ks, vs, ws))
    return ys.swapaxes(0, 1).reshape(b, t, h, n), final


def rwkv6_block(params, cfg, x, *, state=None):
    """Time-mix + channel-mix, with the block's own pre-norms (the caller
    adds no norm/residual — this block returns the full residual delta).
    state: None or dict(tm_last, cm_last, wkv)."""
    b, t, d = x.shape
    dt_ = x.dtype
    h, n = cfg.ssm_heads, cfg.ssm_state

    a = rmsnorm(x, params["ln1"], cfg.norm_eps)
    tm_last = (jnp.zeros((b, 1, d), jnp.float32) if state is None
               else state["tm_last"])
    shifted = _token_shift(a, tm_last)
    mix = params["mix"].astype(dt_)

    def mixed(i):
        m = mix[i][None, None]
        return a + m * (shifted - a)

    xr, xk, xv, xw, xg = (mixed(i) for i in range(5))
    r = (xr @ params["w_r"].astype(dt_)).reshape(b, t, h, n)
    k = (xk @ params["w_k"].astype(dt_)).reshape(b, t, h, n)
    v = (xv @ params["w_v"].astype(dt_)).reshape(b, t, h, n)
    g = jax.nn.silu(xg @ params["w_g"].astype(dt_))
    w_raw = (xw @ params["w_decay"].astype(dt_)).astype(jnp.float32) \
        + params["decay_bias"]
    log_w = -jnp.exp(w_raw).reshape(b, t, h, n)        # < 0

    wkv0 = None if state is None else state["wkv"]
    with jax.named_scope("wkv"):
        o, new_wkv = wkv6_chunked(r, k, v, log_w, params["bonus_u"], wkv0)
    o = rmsnorm(o.reshape(b, t, d), params["ln_x"], cfg.norm_eps) * g
    tm_out = o @ params["w_o"].astype(dt_)

    x2 = x + tm_out
    b2 = rmsnorm(x2, params["ln2"], cfg.norm_eps)
    cm_last = (jnp.zeros((b, 1, d), jnp.float32) if state is None
               else state["cm_last"])
    shifted2 = _token_shift(b2, cm_last)
    cmix = params["cm_mix"].astype(dt_)
    xk2 = b2 + cmix[0][None, None] * (shifted2 - b2)
    xr2 = b2 + cmix[1][None, None] * (shifted2 - b2)
    kk = jnp.square(jax.nn.relu(xk2 @ params["cm_k"].astype(dt_)))
    cm_out = jax.nn.sigmoid(xr2 @ params["cm_r"].astype(dt_)) * \
        (kk @ params["cm_v"].astype(dt_))

    new_state = {
        "tm_last": a[:, -1:].astype(jnp.float32),
        "cm_last": b2[:, -1:].astype(jnp.float32),
        "wkv": new_wkv,
    }
    # returns the *residual update* (block output to be added to x by caller)
    return tm_out + cm_out, new_state


def rwkv6_init_state(cfg, batch: int):
    d = cfg.d_model
    h, n = cfg.ssm_heads, cfg.ssm_state
    return {
        "tm_last": jnp.zeros((batch, 1, d), jnp.float32),
        "cm_last": jnp.zeros((batch, 1, d), jnp.float32),
        "wkv": jnp.zeros((batch, h, n, n), jnp.float32),
    }
