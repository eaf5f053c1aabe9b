"""Plain RWKV-6 (Finch, arXiv:2404.05892) stack as the trainer builds it,
training loss only, with the WKV recurrence evaluated one token at a time.

Per layer, on the residual stream x:

  a = RMSNorm(x, ln1);  a' = a shifted one token (zeros first)
  x_c = a + mix_c * (a' - a)                       c in r, k, v, w, g
  r, k, v = x_r W_r, x_k W_k, x_v W_v  (heads of N)    g = SiLU(x_g W_g)
  w_t = exp(-exp(x_w W_decay + decay_bias))        per channel, in (0, 1)
  o_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
  S_t = diag(w_t) S_{t-1} + k_t v_t^T              S_0 = 0, N x N per head
  x2 = x + (RMSNorm(o, ln_x) * g) W_o
  b = RMSNorm(x2, ln2);  b' = b shifted
  k' = b + cm_mix_0 (b' - b);  r' = b + cm_mix_1 (b' - b)
  x  <- x2 + sigmoid(r' W_cr) * (relu(k' W_ck)^2 W_cv)

then RMSNorm(final_norm), the untied head ``lm_head`` and mean token
cross-entropy. Departures from the release that the trainer makes, and
this reference follows (the configuration file lists them under
``assumed``): a dense ``w_decay`` for the decay LoRA, a static token-shift
mix for the data-dependent one, RMSNorm for LayerNorm, and ``ln_x`` over
the whole width in place of a per-head GroupNorm.

``rnd.product`` rounds both operands and the result of every projection,
as in ``qwen3.py``; ``rnd.f32`` rounds the recurrence, which the trainer
keeps in float32: the decays, the state after every token and each
output (identity for the reference, bfloat16 for the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common as C

# the published keys cut so that the tests run the cell on the CPU in
# seconds (bench/tests/tiny.py)
TINY = {"hidden_size": 64, "head_size": 16, "intermediate_size": 128,
        "vocab_size": 512, "num_hidden_layers": 2}

_ONES = ("final_norm", "ln1", "ln2", "ln_x")


def init(shapes, key):
    def leaf(path, s):
        name = C.path_str(path)
        last = name.rsplit("/", 1)[-1]
        if last in _ONES:
            return jnp.ones(s.shape, s.dtype)
        k = C.leaf_key(key, name)
        if last in ("mix", "cm_mix"):
            x = jax.random.uniform(k, s.shape, jnp.float32)
        elif last == "decay_bias":
            x = jax.random.uniform(k, s.shape, jnp.float32, -6.0, -2.0)
        elif last == "bonus_u":
            x = 0.1 * jax.random.normal(k, s.shape, jnp.float32)
        elif last == "w_decay":
            x = 0.01 * jax.random.normal(k, s.shape, jnp.float32)
        elif last == "embed":
            x = s.shape[-1] ** -0.5 * jax.random.normal(k, s.shape)
        elif last == "lm_head":
            x = s.shape[0] ** -0.5 * jax.random.normal(k, s.shape)
        else:                                    # (L, fan_in, fan_out)
            x = s.shape[1] ** -0.5 * jax.random.normal(k, s.shape)
        return x.astype(s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _shift(a):
    return jnp.concatenate([jnp.zeros_like(a[:, :1]), a[:, :-1]], axis=1)


def wkv_sequential(r, k, v, w, u, rnd=C.exact):
    """r, k, v, w: (B, T, H, N); u: (H, N). One token per scan step;
    ``rnd`` rounds the decays, the state and the outputs."""
    b, _, h, n = r.shape

    def step(s, inp):
        rt, kt, vt, wt = inp                       # (B, H, N)
        kv = kt[..., :, None] * vt[..., None, :]   # (B, H, N, N)
        o = jnp.einsum("bhn,bhnm->bhm", rt, s + u[None, :, :, None] * kv,
                       precision=C.HIGHEST)
        return rnd(wt[..., :, None] * s + kv), rnd(o)

    s0 = jnp.zeros((b, h, n, n), jnp.float32)
    xs = tuple(jnp.swapaxes(a, 0, 1) for a in (r, k, v, rnd(w)))
    _, o = lax.scan(step, s0, xs)
    return jnp.swapaxes(o, 0, 1)


def logits_and_loss(params, tokens, labels, conf: dict, rnd=C.EXACT):
    eps = conf["layer_norm_epsilon"]
    n = conf["head_size"]
    d = conf["hidden_size"]
    h = d // n
    mm = C.matmul(rnd.product)
    x = params["embed"][tokens]
    bsz, t = tokens.shape
    for i in range(conf["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        a = C.rmsnorm(x, lp["ln1"], eps)
        sa = _shift(a)
        xr, xk, xv, xw, xg = (a + lp["mix"][j] * (sa - a) for j in range(5))
        r = mm("btd,de->bte", xr, lp["w_r"]).reshape(bsz, t, h, n)
        k = mm("btd,de->bte", xk, lp["w_k"]).reshape(bsz, t, h, n)
        v = mm("btd,de->bte", xv, lp["w_v"]).reshape(bsz, t, h, n)
        g = jax.nn.silu(mm("btd,de->bte", xg, lp["w_g"]))
        w = jnp.exp(-jnp.exp(mm("btd,de->bte", xw, lp["w_decay"])
                             + lp["decay_bias"])).reshape(bsz, t, h, n)
        o = wkv_sequential(r, k, v, w, lp["bonus_u"],
                           rnd.f32).reshape(bsz, t, d)
        o = C.rmsnorm(o, lp["ln_x"], eps) * g
        x2 = x + mm("btd,de->bte", o, lp["w_o"])
        b = C.rmsnorm(x2, lp["ln2"], eps)
        sb = _shift(b)
        kk = b + lp["cm_mix"][0] * (sb - b)
        rr = b + lp["cm_mix"][1] * (sb - b)
        ff = jnp.square(jax.nn.relu(mm("btd,df->btf", kk, lp["cm_k"])))
        x = x2 + jax.nn.sigmoid(mm("btd,de->bte", rr, lp["cm_r"])) * \
            mm("btf,fd->btd", ff, lp["cm_v"])
    x = C.rmsnorm(x, params["final_norm"], eps)
    logits = mm("btd,dv->btv", x, params["lm_head"])
    return C.cross_entropy(logits, labels)
