"""repro.serve invariants: page allocator conservation, windowed-gather
coverage, sampling policies, the bounded-staleness replica, and the
continuous-batching scheduler (deterministic + hypothesis property tests
over a fake engine, mirroring the delivery-ring conservation tests), plus
real-model engine checks (windowed Pallas kernel path, replica-backed
serving, unsupported-arch validation).

The scheduler property tests exploit the actor/step-engine split: the pump
only speaks the `StepEngine` verb surface (``can_admit``/``start``/``step``/
``finish``), so a host-only fake engine with a REAL `PageAllocator` checks
the scheduling invariants without touching jax:

  * every admitted request completes exactly once, with exactly ``max_new``
    tokens,
  * the page pool is fully restored afterwards (no leak, no double-free —
    `PageAllocator.check` would raise),
  * the per-step active batch never exceeds the slot capacity,
  * admission is FIFO (no skip-ahead past a blocked head),
  * the bounded queue rejects overflow instead of growing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as TF
from repro.models.params import init_params
from repro.serve import (ContinuousScheduler, PagedCacheConfig,
                         PageAllocator, ParamReplica, Request, SampleConfig,
                         StepEngine, sample_tokens, validate_paged_support)
from repro.serve import paged_cache as PC
from repro.serve.sampling import greedy_tokens

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # containers without hypothesis: CI still runs these
    HAVE_HYPOTHESIS = False

FLAGS = TF.RunFlags(remat=False, kv_cache_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

def test_allocator_all_or_nothing():
    pcfg = PagedCacheConfig(page_size=4, num_pages=4, max_requests=2,
                            max_pages_per_seq=4)
    a = PageAllocator(pcfg)
    got = a.alloc("a", 3)
    assert got is not None and len(got) == 3
    assert a.n_free == 1 and not a.can_alloc(2)
    assert a.alloc("b", 2) is None          # refused whole, nothing taken
    assert a.n_free == 1
    a.check()
    assert a.free("a") == 3
    assert a.n_free == 4
    a.check()


def test_allocator_misuse_raises():
    pcfg = PagedCacheConfig(page_size=4, num_pages=4, max_requests=2,
                            max_pages_per_seq=4)
    a = PageAllocator(pcfg)
    a.alloc("a", 1)
    with pytest.raises(ValueError):
        a.alloc("a", 1)                     # rid already holds pages
    with pytest.raises(ValueError):
        a.alloc("b", 0)
    a.free("a")
    with pytest.raises(ValueError):
        a.free("a")                         # double free
    with pytest.raises(ValueError):
        pcfg.pages_needed(17)               # > max_pages_per_seq * page_size


# ---------------------------------------------------------------------------
# windowed gather coverage
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ps,window,n_table", [
    (4, 6, 8), (8, 16, 4), (8, 7, 2), (32, 96, 4), (16, 16, 1)])
def test_window_slots_cover_live_keys(ps, window, n_table):
    """The static slice [start*ps, (start+n_win)*ps) must contain every live
    key position [max(0, pos-window+1), pos] for every pos in the table."""
    pcfg = PagedCacheConfig(page_size=ps, num_pages=n_table,
                            max_requests=1, max_pages_per_seq=n_table)
    pos = jnp.arange(n_table * ps)
    start, n_win = PC.window_slots(pos, window, pcfg, n_table)
    base = np.asarray(start) * ps
    lo = np.maximum(0, np.asarray(pos) - window + 1)
    assert n_win <= n_table
    assert (base <= lo).all()
    assert (np.asarray(pos) <= base + n_win * ps - 1).all()


def test_gather_all_is_dense_layout():
    """In-order pages reassemble the dense cache exactly (parity path)."""
    pcfg = PagedCacheConfig(page_size=4, num_pages=3, max_requests=1,
                            max_pages_per_seq=3)
    pages = jnp.arange((3 + 1) * 4, dtype=jnp.float32).reshape(4, 4, 1, 1)
    table = jnp.asarray([[0, 1, 2]], jnp.int32)
    out = PC.gather_all(pages, table)
    assert out.shape == (1, 12, 1, 1)
    np.testing.assert_array_equal(np.asarray(out).ravel(), np.arange(12))


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_policies():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (3, 17))
    greedy = sample_tokens(logits, SampleConfig())          # key not needed
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(greedy_tokens(logits)))
    sc = SampleConfig(temperature=0.7, top_k=4)
    k1 = jax.random.PRNGKey(7)
    a = sample_tokens(logits, sc, k1)
    b = sample_tokens(logits, sc, k1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every draw stays inside the top-k set
    topk = np.argsort(np.asarray(logits), axis=-1)[:, -4:]
    for trial in range(8):
        t = sample_tokens(logits, sc, jax.random.PRNGKey(100 + trial))
        for r, tok in enumerate(np.asarray(t)):
            assert tok in topk[r]
    # top_k=1 is greedy regardless of temperature
    one = sample_tokens(logits, SampleConfig(temperature=2.0, top_k=1),
                        jax.random.PRNGKey(9))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(greedy))


# ---------------------------------------------------------------------------
# bounded-staleness replica
# ---------------------------------------------------------------------------

def _versioned(v: float):
    return {"w": jnp.full((3,), float(v), jnp.float32)}


@pytest.mark.parametrize("schedule", ["uniform", "straggler", "crash"])
def test_replica_staleness_bound(schedule):
    tau = 3
    rep = ParamReplica(_versioned(0), tau, schedule=schedule, seed=11)
    for v in range(1, 40):
        rep.publish(_versioned(v), v)
        if v % 2 == 0:
            rep.refresh()
        assert 0 <= rep.staleness <= tau
        served = rep.serving_params()
        # the served snapshot is exactly the serving_version's params
        assert float(served["w"][0]) == float(rep.serving_version)
    assert rep.latest_version == 39


def test_replica_tau_zero_always_latest():
    rep = ParamReplica(_versioned(0), 0)
    for v in range(1, 10):
        rep.publish(_versioned(v))
        assert rep.staleness == 0
        assert float(rep.serving_params()["w"][0]) == float(v)


def test_replica_publish_must_advance_by_one():
    rep = ParamReplica(_versioned(0), 2)
    rep.publish(_versioned(1), 1)
    with pytest.raises(ValueError):
        rep.publish(_versioned(5), 5)
    with pytest.raises(ValueError):
        ParamReplica(_versioned(0), -1)


# ---------------------------------------------------------------------------
# scheduler over a fake engine (host-only, real allocator)
# ---------------------------------------------------------------------------

class FakeEngine:
    """StepEngine verb surface without a model: tokens are synthetic, pages
    come from a real `PageAllocator` so conservation bugs surface."""

    def __init__(self, pcfg: PagedCacheConfig):
        self.pcfg = pcfg
        self.alloc = PageAllocator(pcfg)
        self.active = np.zeros(pcfg.max_requests, bool)
        self._slot_of: dict = {}
        self.steps = 0
        self.max_active = 0

    def has_slot(self) -> bool:
        return int(self.active.sum()) < self.pcfg.max_requests

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        total = prompt_len + max_new
        if total > self.pcfg.max_pages_per_seq * self.pcfg.page_size:
            raise ValueError("request exceeds per-request capacity")
        return self.has_slot() and self.alloc.can_alloc(
            self.pcfg.pages_needed(total))

    def start(self, rid, prompt, max_new):
        pages = self.alloc.alloc(rid, self.pcfg.pages_needed(
            len(prompt) + max_new))
        assert pages is not None
        slot = int(np.flatnonzero(~self.active)[0])
        self.active[slot] = True
        self._slot_of[rid] = slot
        self.max_active = max(self.max_active, int(self.active.sum()))
        return np.asarray([9000 + rid], np.int32)

    def step(self):
        self.steps += 1
        self.max_active = max(self.max_active, int(self.active.sum()))
        return np.arange(self.pcfg.max_requests, dtype=np.int32) * 1000 \
            + self.steps

    def finish(self, rid) -> None:
        slot = self._slot_of.pop(rid)
        self.alloc.free(rid)
        self.active[slot] = False

    def slot_of(self, rid) -> int:
        return self._slot_of[rid]


def _check_run(engine: FakeEngine, sched: ContinuousScheduler, toks: dict,
               reqs: list):
    admitted = [r for r in reqs if r.rid in sched.completions]
    assert len(toks) == len(admitted)
    for req in admitted:
        comp = sched.completions[req.rid]
        assert comp.tokens is not None and len(comp.tokens) == req.max_new
        assert comp.tokens[0] == 9000 + req.rid       # the prefill token
        assert req.arrival <= comp.admitted <= comp.finished
    engine.alloc.check()
    assert engine.alloc.n_free == engine.pcfg.num_pages
    assert engine.max_active <= engine.pcfg.max_requests
    assert not sched._live and not sched.queue


def test_scheduler_mixed_trace_deterministic():
    pcfg = PagedCacheConfig(page_size=4, num_pages=6, max_requests=2,
                            max_pages_per_seq=3)
    engine = FakeEngine(pcfg)
    sched = ContinuousScheduler(engine)
    reqs = [Request(rid=i, prompt=np.zeros(p, np.int32), max_new=g,
                    arrival=a)
            for i, (p, g, a) in enumerate(
                [(4, 3, 0), (8, 4, 0), (2, 1, 1), (5, 6, 2), (1, 2, 9)])]
    toks = sched.run(reqs)
    _check_run(engine, sched, toks, reqs)
    assert sched.rejected == 0
    p50, p99 = sched.latency_percentiles()
    assert 0 < p50 <= p99


def test_scheduler_fifo_no_skip_ahead():
    """A small request must not jump past a blocked queue head."""
    pcfg = PagedCacheConfig(page_size=4, num_pages=2, max_requests=2,
                            max_pages_per_seq=2)
    engine = FakeEngine(pcfg)
    sched = ContinuousScheduler(engine)
    reqs = [Request(rid=0, prompt=np.zeros(1, np.int32), max_new=3),
            Request(rid=1, prompt=np.zeros(4, np.int32), max_new=4),
            Request(rid=2, prompt=np.zeros(1, np.int32), max_new=1)]
    for req in reqs:
        sched.submit(req)
    sched.step()
    # rid 0 holds 1 page; head rid 1 needs 2 (blocked); rid 2 would fit but
    # must wait behind the head
    assert 0 in sched._live and 1 not in sched._live and 2 not in sched._live
    while sched.queue or sched._live:
        sched.step()
    toks = sched.drain()
    _check_run(engine, sched, toks, reqs)
    assert sorted(toks) == [0, 1, 2]
    # FIFO: rid 1 admitted no later than rid 2
    assert sched.completions[1].admitted <= sched.completions[2].admitted


def test_scheduler_bounded_queue_rejects():
    pcfg = PagedCacheConfig(page_size=4, num_pages=1, max_requests=1,
                            max_pages_per_seq=1)
    sched = ContinuousScheduler(FakeEngine(pcfg), queue_limit=2)
    accepted = [sched.submit(Request(rid=i, prompt=np.zeros(1, np.int32),
                                     max_new=1)) for i in range(5)]
    assert accepted == [True, True, False, False, False]
    assert sched.rejected == 3


def test_scheduler_drain_single_host_fetch(monkeypatch):
    """The whole run — every decode step plus drain — performs exactly ONE
    device->host fetch.  Pins the coalesced ``jax.device_get((stacked,
    first_toks))`` in `ContinuousScheduler.drain` against regressing back
    to per-request ``np.asarray`` pulls (one blocking sync each, flagged
    by `repro.analysis`'s transfer detector)."""
    calls = []
    real_get = jax.device_get

    def counting_get(x):
        calls.append(x)
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    pcfg = PagedCacheConfig(page_size=4, num_pages=6, max_requests=2,
                            max_pages_per_seq=3)
    engine = FakeEngine(pcfg)
    sched = ContinuousScheduler(engine)
    reqs = [Request(rid=i, prompt=np.zeros(p, np.int32), max_new=g,
                    arrival=a)
            for i, (p, g, a) in enumerate(
                [(4, 3, 0), (8, 4, 0), (2, 1, 1), (5, 6, 2)])]
    toks = sched.run(reqs)
    _check_run(engine, sched, toks, reqs)
    assert len(calls) == 1, (
        f"expected one coalesced drain fetch, saw {len(calls)} device_get "
        f"calls across the run")
    stacked, firsts = calls[0]          # the one fetch carries everything
    assert stacked.shape[0] == engine.steps
    assert sorted(firsts) == sorted(r.rid for r in reqs)


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.data())
    def test_scheduler_properties(data):
        ps = data.draw(st.sampled_from([4, 8]), label="page_size")
        max_pages = data.draw(st.integers(1, 4), label="max_pages_per_seq")
        slots = data.draw(st.integers(1, 3), label="slots")
        num_pages = data.draw(st.integers(max_pages, 3 * max_pages),
                              label="num_pages")
        cap = max_pages * ps
        n = data.draw(st.integers(1, 10), label="n_requests")
        reqs = []
        for i in range(n):
            g = data.draw(st.integers(1, cap - 1), label=f"gen{i}")
            p = data.draw(st.integers(1, cap - g), label=f"prompt{i}")
            a = data.draw(st.integers(0, 15), label=f"arrival{i}")
            reqs.append(Request(rid=i, prompt=np.zeros(p, np.int32),
                                max_new=g, arrival=a))
        pcfg = PagedCacheConfig(page_size=ps, num_pages=num_pages,
                                max_requests=slots,
                                max_pages_per_seq=max_pages)
        engine = FakeEngine(pcfg)
        sched = ContinuousScheduler(engine, queue_limit=64)
        toks = sched.run(reqs, max_steps=5000)
        # every request fits per-request capacity, so all must complete
        assert len(toks) == n
        _check_run(engine, sched, toks, reqs)


# ---------------------------------------------------------------------------
# real-model engine paths
# ---------------------------------------------------------------------------

def test_validate_paged_support():
    assert validate_paged_support(get_config("qwen3-1.7b").reduced()) == 0
    with pytest.raises(NotImplementedError):
        validate_paged_support(get_config("zamba2-7b").reduced())  # SSM
    gemma = dataclasses.replace(get_config("gemma3-27b").reduced(),
                                n_layers=5, global_every=2)
    with pytest.raises(NotImplementedError):
        validate_paged_support(gemma)       # non-uniform local:global mix


def test_serving_refuses_latent_attention():
    """MLA (Moonlight) has no latent cache: paged serving and the dense
    loop's prefill / decode refuse it, naming it."""
    cfg = get_config("moonshot-v1-16b-a3b").reduced()
    with pytest.raises(NotImplementedError, match="MLA"):
        validate_paged_support(cfg)
    params = init_params(TF.model_defs(cfg), jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="moonshot-v1-16b-a3b"):
        TF.prefill(cfg, params, {"tokens": tokens}, 16)
    with pytest.raises(NotImplementedError, match="latent attention"):
        TF.decode_step(cfg, params, {"pos": jnp.zeros((), jnp.int32)},
                       tokens[:, :1])


def _run_engine(engine, prompts, gens):
    sched = ContinuousScheduler(engine)
    trace = [Request(rid=i, prompt=p, max_new=g, arrival=0)
             for i, (p, g) in enumerate(zip(prompts, gens))]
    return sched.run(trace)


def _windowed_setup():
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              sliding_window=96)
    assert validate_paged_support(cfg) == 96
    params = init_params(TF.model_defs(cfg), jax.random.PRNGKey(2))
    pcfg = PagedCacheConfig(page_size=32, num_pages=8, max_requests=2,
                            max_pages_per_seq=4)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=32, dtype=np.int32)
               for _ in range(2)]
    return cfg, params, pcfg, prompts


def test_windowed_engine_kernel_matches_oracle():
    """ps=32 / window=96 makes the windowed gather exactly 128 keys wide, so
    ``attn_impl="kernel"`` genuinely runs the Pallas swa kernel (interpret
    mode) on the paged decode hot path.  Along one teacher-forced token
    trajectory, its decode logits and the masked-chunk oracle's must both
    agree with a float32 reference engine (float32 activations and cache,
    masked-chunk attention, highest matmul precision), and with each other.

    Logits, not tokens: with random weights the top two logits can sit
    closer than the bf16 path's own error (0.014 apart at one step of this
    trajectory), so greedy tokens may legitimately differ."""
    from repro.serve.engine import (make_paged_decode_logits,
                                    make_paged_prefill_logits)
    cfg, params, pcfg, prompts = _windowed_setup()
    f32 = TF.RunFlags(remat=False, kv_cache_dtype=jnp.float32,
                      compute_dtype=jnp.float32)
    variants = {"oracle": (FLAGS, "ref"), "kernel": (FLAGS, "kernel"),
                "f32": (f32, "ref")}
    table = np.full((2, 4), pcfg.scratch_page, np.int32)
    table[0, :2], table[1, :2] = [0, 1], [2, 3]
    state, decode = {}, {}
    with jax.default_matmul_precision("highest"):
        for name, (flags, impl) in variants.items():
            prefill = jax.jit(make_paged_prefill_logits(cfg, pcfg, 1, flags))
            pools = PC.init_page_pool(cfg.n_layers, cfg.n_kv_heads,
                                      cfg.resolved_head_dim, pcfg,
                                      flags.kv_cache_dtype)
            for r, prompt in enumerate(prompts):
                logits, *pools = prefill(params, *pools, prompt[None],
                                         np.int32(32), table[r, :1])
            state[name] = (pools, jnp.full((2,), 32, jnp.int32))
            decode[name] = jax.jit(make_paged_decode_logits(
                cfg, pcfg, flags, window=96, attn_impl=impl))
        tokens = jnp.asarray([7, 11], jnp.int32)
        for _ in range(16):
            out = {}
            for name, ((kp, vp), pos) in state.items():
                logits, pos, kp, vp = decode[name](
                    params, kp, vp, tokens, pos, table, jnp.ones(2, bool))
                state[name] = ((kp, vp), pos)
                out[name] = np.asarray(logits)
            # bf16 activations (2^-9 relative rounding per op) through two
            # layers put ~1% on logits of magnitude ~3: both paths sit
            # within 0.031 of float32 here, a wrong mask or scale is O(1)
            for a, b in (("kernel", "f32"), ("oracle", "f32"),
                         ("kernel", "oracle")):
                np.testing.assert_allclose(out[a], out[b], atol=5e-2,
                                           rtol=0, err_msg=f"{a} vs {b}")
            tokens = jnp.asarray(np.argmax(out["oracle"], -1), jnp.int32)


@pytest.mark.parametrize("attn_impl", ["ref", "kernel"])
def test_windowed_engine_runs_are_deterministic(attn_impl):
    """Two runs of one engine configuration emit the same tokens, and every
    request completes.  The device mirrors of the host page table and
    positions are uploaded from copies: an upload that aliased the numpy
    buffers the engine mutates right after dispatch made runs differ."""
    cfg, params, pcfg, prompts = _windowed_setup()
    gens = [12, 20]
    runs = []
    for _ in range(2):
        engine = StepEngine(cfg, params, pcfg, FLAGS, attn_impl=attn_impl)
        runs.append(_run_engine(engine, prompts, gens))
        engine.alloc.check()
    for rid, g in enumerate(gens):
        assert len(runs[0][rid]) == g
        np.testing.assert_array_equal(runs[0][rid], runs[1][rid])


def test_replica_backed_engine_serves_within_bound():
    cfg = get_config("qwen3-1.7b").reduced()
    params = init_params(TF.model_defs(cfg), jax.random.PRNGKey(3))
    tau = 2
    replica = ParamReplica(params, tau, schedule="uniform", seed=1)
    pcfg = PagedCacheConfig(page_size=8, num_pages=4, max_requests=1,
                            max_pages_per_seq=2)
    engine = StepEngine(cfg, params, pcfg, FLAGS, replica=replica)
    sched = ContinuousScheduler(engine)
    rng = np.random.default_rng(6)
    sched.submit(Request(
        rid=0, prompt=rng.integers(0, cfg.vocab_size, size=8,
                                   dtype=np.int32), max_new=8))
    v = 0
    while sched.queue or sched._live:
        v += 1
        replica.publish(params, v)          # trainer advances every step
        if v % 2 == 0:
            replica.refresh()
        sched.step()
        assert 0 <= replica.staleness <= tau
        assert v < 100
    toks = sched.drain()
    assert len(toks[0]) == 8
    engine.alloc.check()
