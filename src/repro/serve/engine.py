"""Step engine: jitted paged-cache decode + per-request host bookkeeping.

The actor/step-engine split the ROADMAP prescribes: `StepEngine` owns the
device state (params or a staleness-bounded `ParamReplica`, the paged KV
pools, one compiled decode program) and exposes exactly three verbs —
``start`` (prefill + page allocation), ``step`` (one decode step for every
active slot), ``finish`` (free pages/slot).  Admission policy, queues and
completion tracking live in `repro.serve.scheduler`.

Parity by construction with the dense legacy loop
(`repro.dist.train.make_decode_step`):

  * the pre-attention math is literally the same code
    (`repro.models.layers.project_qkv`),
  * full attention gathers the whole page table, which with in-order pages
    reproduces the dense ``(R, T, K, hd)`` cache layout — same shapes, same
    masked positions, so the decode step is bitwise-identical per request
    when ``max_pages_per_seq * page_size`` equals the dense ``max_len``,
  * windowed layers gather only the ``ceil(window/ps) + 1`` live pages per
    request and run the `swa_attention` kernel (or the masked-chunk oracle)
    with per-request positions and page-base offsets — the hot path never
    reads a dead page.

One decode program serves every mix of requests: inactive slots write to the
pool's scratch page and their rows are positionally masked, so admission and
eviction never recompile.  Prefill compiles once per page-count bucket
(prompts pad to a page multiple).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, BLOCK_ATTN, FRONTEND_NONE
from repro.kernels.swa_attention import ops as SWA
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import scan_utils as SU
from repro.models import transformer as TF
from repro.serve import paged_cache as PC
from repro.serve.paged_cache import PagedCacheConfig, PageAllocator
from repro.serve.replica import ParamReplica
from repro.serve.sampling import SampleConfig, sample_tokens


def validate_paged_support(cfg: ArchConfig) -> int:
    """Paged serving supports uniform-window attention stacks; returns the
    (single) window size.  Grouped local:global (gemma3), SSM and frontend
    archs keep the dense legacy loop; latent attention (MLA) has no cache
    here at all."""
    why = TF.serving_refusal(cfg)
    if why:
        raise NotImplementedError(f"paged serving: {why}")
    if cfg.block_type != BLOCK_ATTN:
        raise NotImplementedError(
            f"paged serving needs an attention stack, got {cfg.block_type}")
    if cfg.frontend != FRONTEND_NONE:
        raise NotImplementedError("paged serving: token frontends only")
    windows = set(cfg.layer_window_sizes())
    if len(windows) != 1:
        raise NotImplementedError(
            f"paged serving needs a uniform window, got {sorted(windows)}")
    return windows.pop()


# ---------------------------------------------------------------------------
# jitted step builders
# ---------------------------------------------------------------------------

def _attend_full(cfg, q, kp, vp, table, pos, positions, dt):
    """Full-table gather + masked-chunk attention (the parity path)."""
    r = q.shape[0]
    hd = cfg.resolved_head_dim
    nk = cfg.n_kv_heads
    keys = PC.gather_all(kp, table).astype(dt)
    vals = PC.gather_all(vp, table).astype(dt)
    t = keys.shape[1]
    k_pos = jnp.arange(t)[None]
    k_pos = jnp.where(k_pos <= pos[:, None], k_pos, -1)
    q5 = q.reshape(r, 1, nk, cfg.n_heads // nk, hd)
    out = L.masked_attn_chunk(q5, keys, vals, positions, k_pos, 0,
                              hd ** -0.5)
    return out.reshape(r, 1, cfg.n_heads, hd).astype(dt)


def _attend_window(cfg, pcfg, q, kp, vp, table, pos, positions, dt, *,
                   window: int, attn_impl: str):
    """Windowed gather (live pages only) + the swa decode kernel or its
    oracle, the masked chunk (`kernels.swa_attention.ops.resolve` picks)."""
    r = q.shape[0]
    hd = cfg.resolved_head_dim
    nk = cfg.n_kv_heads
    n_table = table.shape[1]
    start, n_win = PC.window_slots(pos, window, pcfg, n_table)
    keys, base = PC.gather_window(kp, table, start, n_win)
    vals, _ = PC.gather_window(vp, table, start, n_win)
    keys, vals = keys.astype(dt), vals.astype(dt)
    t = keys.shape[1]
    use_kernel, interpret = SWA.resolve(t, attn_impl)
    if use_kernel:
        q4 = q[:, 0].reshape(r, nk, cfg.n_heads // nk, hd)
        out = SWA.decode_kernel(q4, keys, vals, pos, base, window=window,
                                interpret=interpret)
        return out.reshape(r, 1, cfg.n_heads, hd).astype(dt)
    k_pos = base[:, None] + jnp.arange(t)[None]
    k_pos = jnp.where(k_pos <= pos[:, None], k_pos, -1)
    q5 = q.reshape(r, 1, nk, cfg.n_heads // nk, hd)
    out = L.masked_attn_chunk(q5, keys, vals, positions, k_pos, window,
                              hd ** -0.5)
    return out.reshape(r, 1, cfg.n_heads, hd).astype(dt)


def make_paged_decode_logits(cfg: ArchConfig, pcfg: PagedCacheConfig,
                             flags: TF.RunFlags = TF.DEFAULT_FLAGS, *,
                             window: int = 0, attn_impl: str = "auto"):
    """``(params, k_pool, v_pool, tokens (R,), pos (R,), table, active) ->
    (logits (R, V) f32, pos (R,), k_pool, v_pool)`` — one decode step for
    all R request slots (donate the pools).  Mirrors
    `repro.models.transformer.decode_step` layer for layer, with the dense
    cache update swapped for a page scatter/gather.  ``pos`` is advanced
    in-jit for active slots so the hot loop never re-uploads it."""
    ps = pcfg.page_size
    r, n_table = pcfg.max_requests, pcfg.max_pages_per_seq

    def step(params, k_pool, v_pool, tokens, pos, table, active):
        x = jnp.take(params["embed"], tokens[:, None],
                     axis=0).astype(flags.compute_dtype)      # (R, 1, d)
        positions = pos[:, None]
        cur_slot = jnp.minimum(pos // ps, n_table - 1)
        page_idx = jnp.where(active, table[jnp.arange(r), cur_slot],
                             pcfg.scratch_page)
        offset = pos % ps

        def body(carry, scanned):
            x, aux = carry
            lp, kp, vp = scanned
            dt = x.dtype
            y = L.rmsnorm(x, lp["ln_attn"], cfg.norm_eps)
            q, k, v = L.project_qkv(lp["attn"], cfg, y, positions)
            kp = PC.write_token_kv(kp, k[:, 0], page_idx, offset)
            vp = PC.write_token_kv(vp, v[:, 0], page_idx, offset)
            if window:
                out = _attend_window(cfg, pcfg, q, kp, vp, table, pos,
                                     positions, dt, window=window,
                                     attn_impl=attn_impl)
            else:
                out = _attend_full(cfg, q, kp, vp, table, pos, positions, dt)
            h = jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"].astype(dt))
            x = TF._constrain(x + h, flags)
            y2 = L.rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
            if cfg.is_moe:
                out2, a = MOE.moe_block(lp["moe"], cfg, y2)
                a = a["aux_loss"]
            else:
                out2, a = L.mlp_block(lp["mlp"], y2), 0.0
            x = TF._constrain(x + out2, flags)
            return (x, aux + a), (kp, vp)

        (x, _), (k_pool, v_pool) = SU.scan(
            body, (x, 0.0), (params["layers"], k_pool, v_pool))
        logits = TF.lm_logits(cfg, params, x)                 # (R, 1, V)
        pos_next = jnp.where(active, pos + 1, pos)
        return logits[:, -1, :], pos_next, k_pool, v_pool

    return step


def make_paged_decode_step(cfg: ArchConfig, pcfg: PagedCacheConfig,
                           flags: TF.RunFlags = TF.DEFAULT_FLAGS, *,
                           window: int = 0,
                           sample: SampleConfig = SampleConfig(),
                           attn_impl: str = "auto",
                           check_finite: bool = False):
    """``(params, k_pool, v_pool, tokens (R,), pos (R,), table, active,
    key) -> (tokens (R,), pos (R,), k_pool, v_pool)``:
    :func:`make_paged_decode_logits` plus sampling.

    ``check_finite`` appends a per-slot ``finite`` (R,) bool output (all
    last-position logits finite) — the quarantine signal.  Off by default
    so the hot path's program stays byte-identical."""
    logits_step = make_paged_decode_logits(cfg, pcfg, flags, window=window,
                                           attn_impl=attn_impl)

    def step(params, k_pool, v_pool, tokens, pos, table, active, key):
        logits, pos_next, k_pool, v_pool = logits_step(
            params, k_pool, v_pool, tokens, pos, table, active)
        out = (sample_tokens(logits, sample, key), pos_next, k_pool, v_pool)
        if check_finite:
            finite = jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                             axis=-1)
            return out + (finite,)
        return out

    return step


def make_paged_prefill_logits(cfg: ArchConfig, pcfg: PagedCacheConfig,
                              bucket_pages: int,
                              flags: TF.RunFlags = TF.DEFAULT_FLAGS):
    """One-request prefill for prompts bucketed to ``bucket_pages`` pages:
    ``(params, k_pool, v_pool, tokens (1, bucket), true_len, page_ids
    (bucket_pages,)) -> (logits (1, V) f32, k_pool, v_pool)``.

    Runs the stock training-path stack (`TF.attn_stack` with collect_kv) on
    the padded prompt — causal masking keeps real positions blind to the
    pad tail — then scatters the collected KV into the request's pages and
    reads logits at the true last position."""
    ps = pcfg.page_size
    bucket = bucket_pages * ps

    def prefill(params, k_pool, v_pool, tokens, true_len, page_ids):
        x = TF.embed_input(cfg, params, {"tokens": tokens},
                           flags)                             # (1, bucket, d)
        positions = jnp.arange(bucket)
        x, _, kvs = TF.attn_stack(cfg, flags, params["layers"], x, positions,
                                  collect_kv=True)
        nl = cfg.n_layers
        k_new, v_new = kvs
        k_new = k_new[:, 0].reshape(nl, bucket_pages, ps, *k_new.shape[3:])
        v_new = v_new[:, 0].reshape(nl, bucket_pages, ps, *v_new.shape[3:])
        k_pool = k_pool.at[:, page_ids].set(k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[:, page_ids].set(v_new.astype(v_pool.dtype))
        last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        logits = TF.lm_logits(cfg, params, last)              # (1, 1, V)
        return logits[:, -1, :], k_pool, v_pool

    return prefill


def make_paged_prefill_step(cfg: ArchConfig, pcfg: PagedCacheConfig,
                            bucket_pages: int,
                            flags: TF.RunFlags = TF.DEFAULT_FLAGS, *,
                            sample: SampleConfig = SampleConfig()):
    """:func:`make_paged_prefill_logits` plus sampling: ``(..., page_ids,
    key) -> (token (1,), k_pool, v_pool)``."""
    logits_step = make_paged_prefill_logits(cfg, pcfg, bucket_pages, flags)

    def prefill(params, k_pool, v_pool, tokens, true_len, page_ids, key):
        logits, k_pool, v_pool = logits_step(params, k_pool, v_pool, tokens,
                                             true_len, page_ids)
        return sample_tokens(logits, sample, key), k_pool, v_pool

    return prefill


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class StepEngine:
    """Device-state owner for continuous-batching serving.

    Host-side state (page tables, per-slot positions, the allocator) is
    plain numpy — it changes on admission/eviction, between jitted calls.
    Device state (pools, last tokens) stays on device across the whole run;
    nothing round-trips to host per token.
    """

    def __init__(self, cfg: ArchConfig, params, pcfg: PagedCacheConfig,
                 flags: TF.RunFlags = TF.DEFAULT_FLAGS, *,
                 sample: SampleConfig = SampleConfig(),
                 attn_impl: str = "auto",
                 replica: ParamReplica | None = None,
                 mesh=None, seed: int = 0,
                 check_finite: bool = False):
        self.cfg, self.pcfg, self.flags = cfg, pcfg, flags
        self.window = validate_paged_support(cfg)
        self.sample = sample
        self.replica = replica
        self.check_finite = check_finite
        self._static_params = params
        self.alloc = PageAllocator(pcfg)
        r, n_table = pcfg.max_requests, pcfg.max_pages_per_seq
        self.table = np.full((r, n_table), pcfg.scratch_page, np.int32)
        self.pos = np.zeros((r,), np.int32)
        self.active = np.zeros((r,), bool)
        self.slot_rid: list = [None] * r
        self._slot_of: dict = {}
        k_pool, v_pool = PC.init_page_pool(
            cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim, pcfg,
            flags.kv_cache_dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.dist.sharding import paged_cache_specs
            spec = paged_cache_specs(cfg, mesh, k_pool)
            k_pool = jax.device_put(k_pool, NamedSharding(mesh, spec))
            v_pool = jax.device_put(v_pool, NamedSharding(mesh, spec))
        self.k_pool, self.v_pool = k_pool, v_pool
        self.tokens = jnp.zeros((r,), jnp.int32)
        # device mirrors of the membership state: pos advances in-jit, and
        # table/active/pos re-upload lazily (one coalesced transfer before
        # the next decode, however many admissions/evictions happened) — the
        # steady-state decode loop dispatches with zero host->device copies
        self._d_pos = jnp.zeros((r,), jnp.int32)
        self._d_table = jnp.asarray(self.table)
        self._d_active = jnp.asarray(self.active)
        self._dirty = False
        self._key = jax.random.PRNGKey(seed)
        self._decode = jax.jit(
            make_paged_decode_step(cfg, pcfg, flags, window=self.window,
                                   sample=sample, attn_impl=attn_impl,
                                   check_finite=check_finite),
            donate_argnums=(1, 2))
        self._prefills: dict = {}
        self._finite = None           # (R,) device bools (check_finite only)
        self.steps = 0

    # -- capacity ----------------------------------------------------------
    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    def has_slot(self) -> bool:
        return self.active_count < self.pcfg.max_requests

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        total = prompt_len + max_new
        if total > self.pcfg.max_pages_per_seq * self.pcfg.page_size:
            raise ValueError(
                f"request of {total} tokens exceeds per-request capacity")
        return self.has_slot() and self.alloc.can_alloc(
            self.pcfg.pages_needed(total))

    # -- params (direct or via the staleness-bounded replica) --------------
    def _params(self):
        if self.replica is not None:
            return self.replica.serving_params()
        return self._static_params

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- verbs -------------------------------------------------------------
    def start(self, rid, prompt: np.ndarray, max_new: int) -> jax.Array:
        """Admit ``rid``: allocate pages + a slot, prefill, emit the first
        token (returned as a device (1,) array — no host sync)."""
        prompt = np.asarray(prompt, np.int32)
        s = int(prompt.shape[0])
        assert s >= 1 and max_new >= 1
        n_pages = self.pcfg.pages_needed(s + max_new)
        bucket_pages = self.pcfg.pages_needed(s)
        pages = self.alloc.alloc(rid, n_pages)
        if pages is None:
            raise RuntimeError("admitted without pages (check can_admit)")
        slot = int(np.flatnonzero(~self.active)[0])
        self.table[slot] = self.pcfg.scratch_page
        self.table[slot, :n_pages] = pages
        if bucket_pages not in self._prefills:
            self._prefills[bucket_pages] = jax.jit(
                make_paged_prefill_step(self.cfg, self.pcfg, bucket_pages,
                                        self.flags, sample=self.sample),
                donate_argnums=(1, 2))
        padded = np.zeros((1, bucket_pages * self.pcfg.page_size), np.int32)
        padded[0, :s] = prompt
        tok, self.k_pool, self.v_pool = self._prefills[bucket_pages](
            self._params(), self.k_pool, self.v_pool, padded,
            np.int32(s), np.asarray(pages[:bucket_pages], np.int32),
            self._next_key())
        self.pos[slot] = s
        self.active[slot] = True
        self.slot_rid[slot] = rid
        self._slot_of[rid] = slot
        self.tokens = self.tokens.at[slot].set(tok[0])
        self._dirty = True
        return tok

    def step(self) -> jax.Array:
        """One decode step for every active slot; returns the (R,) device
        token array (row r is meaningful iff slot r is active)."""
        if self._dirty:
            # host pos mirrors device pos exactly (incremented below in
            # lockstep with the in-jit advance), so one upload restores all
            # three membership arrays after any number of start/finish calls
            self._d_pos = jnp.asarray(self.pos.copy())
            self._d_table = jnp.asarray(self.table.copy())
            self._d_active = jnp.asarray(self.active.copy())
            self._dirty = False
        key = self._key if self.sample.is_greedy else self._next_key()
        out = self._decode(
            self._params(), self.k_pool, self.v_pool, self.tokens,
            self._d_pos, self._d_table, self._d_active, key)
        if self.check_finite:
            toks, self._d_pos, self.k_pool, self.v_pool, self._finite = out
        else:
            toks, self._d_pos, self.k_pool, self.v_pool = out
        self.tokens = toks
        self.pos[self.active] += 1
        self.steps += 1
        return toks

    def nonfinite_rids(self) -> list:
        """Requests whose last decode hit non-finite logits (empty unless
        ``check_finite``) — the scheduler's quarantine signal.  This is the
        one host sync the fault path pays, and only when armed."""
        if not self.check_finite or self._finite is None:
            return []
        flags = np.asarray(self._finite)
        return [self.slot_rid[s] for s in np.flatnonzero(self.active)
                if not flags[s] and self.slot_rid[s] is not None]

    def poison_kv(self, rid) -> None:
        """Fault injection: NaN the request's most recently written KV
        position.  Every live query attends that position, so the next
        decode step's logits go NaN for this slot — exactly the corruption
        the quarantine path must contain (`repro.faults`)."""
        slot = self._slot_of[rid]
        pos = int(self.pos[slot])
        if pos < 1:
            return
        page = int(self.table[slot, (pos - 1) // self.pcfg.page_size])
        off = (pos - 1) % self.pcfg.page_size
        self.k_pool = self.k_pool.at[:, page, off].set(jnp.nan)

    def finish(self, rid) -> None:
        """Evict ``rid``: free its pages and slot."""
        slot = self._slot_of.pop(rid)
        self.alloc.free(rid)
        self.table[slot] = self.pcfg.scratch_page
        self.pos[slot] = 0
        self.active[slot] = False
        self.slot_rid[slot] = None
        self._dirty = True

    def slot_of(self, rid) -> int:
        return self._slot_of[rid]
