"""The overload-safe service layer: clock/deadline seam, admission control
(token bucket, queue bounds, in-flight cap), circuit breakers, the engine
failover ladder with the live-bytes watchdog, plan-cache aging, and the
ledger/receipt reconciliation invariant."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import CommLedger, VFLDataset
from repro.core.comm import CommSchedule
from repro.core.api import CoresetPipeline, FailoverOutcome, build_coreset_streaming
from repro.core.faults import (
    Deadline,
    DeadlineExceeded,
    FaultPlan,
    PartyUnavailable,
    SimClock,
    Transport,
    WallClock,
)
from repro.core.plan import (
    FAILOVER_LADDER,
    CoresetSpec,
    MemoryBudgetExceeded,
    MemoryWatchdog,
    PlanCache,
    compile_plan,
    live_bytes,
)
from repro.serve import CoresetService, InsertReceipt, QueryReceipt, ShedReceipt
from repro.serve.resilience import CircuitBreaker, TokenBucket

BLOCK = 256


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    yield
    jax.clear_caches()


def _chunk(rng, rows=300, dims=(3, 2), labels=True):
    parts = [rng.normal(size=(rows, d)).astype(np.float32) for d in dims]
    y = rng.normal(size=(rows,)).astype(np.float32) if labels else None
    return parts, y


def _ds(rng, n=512, dims=(3, 3)):
    parts = [rng.normal(size=(n, d)).astype(np.float32) for d in dims]
    y = rng.normal(size=(n,)).astype(np.float32)
    return VFLDataset(parts, y)


# --------------------------------------------------------------------------
# Clock / Deadline seam
# --------------------------------------------------------------------------

def test_sim_clock_ticks_and_advances():
    c = SimClock(start=5.0, tick=0.5)
    assert c.now() == 5.0
    assert c.now() == 5.5          # auto-tick per read
    c.advance(2.0)
    assert c.peek() == 8.0         # peek never consumes a tick
    assert c.peek() == 8.0
    with pytest.raises(ValueError):
        c.advance(-1.0)
    with pytest.raises(ValueError):
        SimClock(tick=-0.1)


def test_wall_clock_monotonic_and_advance_noop():
    c = WallClock()
    a = c.now()
    c.advance(1e6)                 # simulated delay never sleeps
    assert c.now() - a < 60.0


def test_deadline_expiry_uses_geq_semantics():
    """A deadline landing EXACTLY on a check boundary counts as missed."""
    c = SimClock(start=0.0, tick=1.0)
    dl = Deadline.after(c, 1.0)    # consumes t=0 -> at=1.0
    # next read is exactly t=1.0: expired, not "one more superchunk"
    assert dl.expired(c)
    with pytest.raises(DeadlineExceeded) as ei:
        dl.check(c, "op")
    assert ei.value.op == "op" and ei.value.at == 1.0
    with pytest.raises(ValueError):
        Deadline.after(c, -1.0)


def test_deadline_remaining_and_zero_budget():
    c = SimClock(tick=0.0)
    dl = Deadline.after(c, 2.5)
    assert dl.remaining(c) == 2.5
    z = Deadline.after(c, 0.0)
    assert z.expired(c)            # zero budget is born expired


def test_transport_advances_bound_clock():
    c = SimClock(tick=0.0)
    # every op delayed, but under timeout_s: pure latency, no retries
    tr = Transport(FaultPlan(seed=0, delay=1.0, delay_s=0.25, timeout_s=1.0,
                             max_retries=0), clock=c)
    tr.deliver(CommSchedule.dis_round1(4), CommLedger())
    assert c.peek() == pytest.approx(tr.stats.sim_time_s)
    assert c.peek() > 0.0


# --------------------------------------------------------------------------
# TokenBucket / CircuitBreaker
# --------------------------------------------------------------------------

def test_token_bucket_burst_refill_and_retry_hint():
    b = TokenBucket(rate_per_s=2.0, burst=2)
    ok1, _ = b.try_take(0.0)
    ok2, _ = b.try_take(0.0)
    ok3, retry = b.try_take(0.0)
    assert (ok1, ok2, ok3) == (True, True, False)
    assert retry == pytest.approx(0.5)     # 1 token / 2 per second
    ok4, _ = b.try_take(0.6)               # refilled 1.2 tokens
    assert ok4
    with pytest.raises(ValueError):
        TokenBucket(0.0, 2)
    with pytest.raises(ValueError):
        TokenBucket(1.0, 0.5)


def test_breaker_full_lifecycle():
    br = CircuitBreaker(threshold=2, cooldown_s=10.0)
    assert br.allow(0.0) == (True, 0.0)
    br.record_failure(0.0, "boom1")
    assert br.state == "closed"            # 1 of 2
    br.record_failure(1.0, "boom2")
    assert br.state == "open" and br.trips == 1
    ok, retry = br.allow(5.0)
    # opened at t=1.0 (the tripping failure), so 6s of cooldown remain
    assert not ok and retry == pytest.approx(6.0)
    ok, _ = br.allow(11.0)                 # cooldown elapsed -> probe
    assert ok and br.state == "half_open"
    ok2, _ = br.allow(11.0)                # only ONE probe in flight
    assert not ok2
    br.record_failure(11.0, "probe died")
    assert br.state == "open" and br.trips == 2
    ok, _ = br.allow(22.0)
    br.record_success()                    # probe succeeded
    assert br.state == "closed" and br.failures == 0


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker(threshold=3, cooldown_s=1.0)
    br.record_failure(0.0, "x")
    br.record_failure(0.0, "x")
    br.record_success()                    # intermittent, not consecutive
    br.record_failure(0.0, "x")
    br.record_failure(0.0, "x")
    assert br.state == "closed" and br.trips == 0


def test_breaker_neutral_requeues_probe_without_trip():
    br = CircuitBreaker(threshold=1, cooldown_s=10.0)
    br.record_failure(0.0, "x")
    assert br.state == "open" and br.trips == 1
    ok, _ = br.allow(20.0)
    assert ok and br.state == "half_open"
    br.record_neutral(20.0)                # e.g. the probe hit a deadline
    assert br.state == "open" and br.trips == 1
    ok, _ = br.allow(31.0)                 # next probe still fires
    assert ok


# --------------------------------------------------------------------------
# MemoryWatchdog + the failover ladder
# --------------------------------------------------------------------------

def test_live_bytes_counts_device_arrays():
    before = live_bytes()
    keep = jax.device_put(np.zeros((256, 256), np.float32))
    assert live_bytes() >= before + keep.nbytes


def test_watchdog_raises_with_census():
    wd = MemoryWatchdog(1)
    keep = jax.device_put(np.zeros(64, np.float32))  # anything live trips it
    with pytest.raises(MemoryBudgetExceeded) as ei:
        wd.check()
    assert ei.value.budget == 1 and ei.value.observed >= keep.nbytes
    assert wd.checks == 1 and wd.peak >= keep.nbytes
    with pytest.raises(ValueError):
        MemoryWatchdog(0)


def test_fallback_chain_follows_ladder():
    rng = np.random.default_rng(0)
    ds = _ds(rng)
    chains = {}
    for engine in ("materialized", "pipelined", "streamed", "batched"):
        spec = CoresetSpec(task="vrlr", budgets=16, engine=engine,
                           block_size=64, chunk_blocks=4,
                           num_seeds=2 if engine == "batched" else 1)
        chains[engine] = compile_plan(spec, ds).fallback_chain
    assert chains["materialized"] == ("pipelined", "streamed")
    assert chains["pipelined"] == ("streamed",)
    assert chains["streamed"] == ()
    assert chains["batched"] == ()
    assert FAILOVER_LADDER == ("materialized", "pipelined", "streamed")


def test_failover_draw_identity_and_ledger_bill():
    """THE acceptance pin: a pipelined build forced over its memory budget
    falls back to streamed bit-identically; the ledger equals the
    successful engine's bill plus a zero-unit fallback/ attribution."""
    rng = np.random.default_rng(1)
    ds = _ds(rng)
    pipe = CoresetPipeline(ds)
    key = jax.random.PRNGKey(3)
    spec = CoresetSpec(task="vrlr", budgets=24, engine="pipelined",
                       block_size=64, chunk_blocks=2)

    led = CommLedger()
    out = pipe.build_failover(spec, key=key, ledger=led,
                              memory_budget_bytes=1)
    assert isinstance(out, FailoverOutcome)
    assert out.fallback == "pipelined->streamed"
    assert out.attempts[0].engine == "pipelined"
    assert "MemoryBudgetExceeded" in out.attempts[0].error
    assert any("failover: pipelined -> streamed" in n
               for n in out.plan.notes)

    led_ref = CommLedger()
    ref = build_coreset_streaming(
        "vrlr", ds, 24, key=key, block_size=64, ledger=led_ref)
    np.testing.assert_array_equal(np.asarray(out.coreset.indices),
                                  np.asarray(ref.indices))
    np.testing.assert_array_equal(np.asarray(out.coreset.weights),
                                  np.asarray(ref.weights))
    assert led.total == led_ref.total
    fb = {t: u for t, u in led.by_tag().items() if t.startswith("fallback/")}
    assert fb == {"fallback/pipelined->streamed": 0}


def test_failover_noop_when_first_engine_succeeds():
    rng = np.random.default_rng(2)
    ds = _ds(rng)
    pipe = CoresetPipeline(ds)
    spec = CoresetSpec(task="vrlr", budgets=16, engine="pipelined",
                       block_size=64, chunk_blocks=2)
    led = CommLedger()
    out = pipe.build_failover(spec, key=jax.random.PRNGKey(0), ledger=led)
    assert out.fallback is None and out.attempts == ()
    assert led.by_prefix("fallback/") == 0
    assert not any("failover" in n for n in out.plan.notes)


def test_failover_passes_engine_independent_errors_through():
    """Deadline and spec errors must not burn ladder rungs."""
    rng = np.random.default_rng(3)
    ds = _ds(rng)
    pipe = CoresetPipeline(ds)
    spec = CoresetSpec(task="vrlr", budgets=16, engine="pipelined",
                       block_size=64, chunk_blocks=2)
    c = SimClock(tick=1.0)
    dl = Deadline.after(c, 0.5)
    led = CommLedger()
    with pytest.raises(DeadlineExceeded):
        pipe.build_failover(spec, key=jax.random.PRNGKey(0), ledger=led,
                            probe=lambda: dl.check(c, "leaf"))
    assert led.total == 0          # rolled back, no fallback entry


# --------------------------------------------------------------------------
# Service: deadlines (edge cases), admission, breakers, failover
# --------------------------------------------------------------------------

def _svc(clock=None, **kw):
    svc = CoresetService(clock=clock, **kw)
    svc.register("t", task="vrlr", budget=16, seed=0, block_size=BLOCK)
    return svc


def test_insert_deadline_expired_at_admission_sheds_with_zero_work():
    clock = SimClock(tick=0.0)
    svc = _svc(clock)
    rng = np.random.default_rng(0)
    parts, y = _chunk(rng)
    r = svc.insert("t", parts, y, deadline=Deadline.after(clock, 0.0))
    assert isinstance(r, ShedReceipt)
    assert r.reason == "deadline" and r.op == "insert"
    st = svc.state("t")
    assert st.tree.num_chunks == 0 and st.ledger.total == 0
    assert st.sheds == 1 and svc.stats()["sheds"] == 1


def test_insert_deadline_mid_build_rolls_back():
    clock = SimClock(tick=1.0)       # every clock read costs a full second
    svc = _svc(clock)
    rng = np.random.default_rng(0)
    parts, y = _chunk(rng)
    ok = svc.insert("t", parts, y)   # no deadline: lands
    assert isinstance(ok, InsertReceipt)
    led_before = svc.state("t").ledger.total
    # admission passes (first read), the leaf probe's read expires it
    r = svc.insert("t", parts, y, deadline=Deadline.after(clock, 1.5))
    assert isinstance(r, ShedReceipt) and r.reason == "deadline"
    st = svc.state("t")
    assert st.tree.num_chunks == 1 and st.ledger.total == led_before


def test_insert_deadline_exactly_at_boundary_sheds():
    """The >= semantics end to end: a deadline landing exactly on the
    superchunk-boundary check is a miss, not a keep-going."""
    clock = SimClock(tick=1.0)
    svc = _svc(clock)
    rng = np.random.default_rng(0)
    parts, y = _chunk(rng)
    # admission consumes t=0; the first probe reads exactly t=1.0 == at
    r = svc.insert("t", parts, y, deadline=Deadline.after(clock, 1.0))
    assert isinstance(r, ShedReceipt) and r.reason == "deadline"
    assert svc.state("t").tree.num_chunks == 0


def test_query_degrades_to_union_under_deadline_pressure():
    clock = SimClock(tick=0.6)
    svc = _svc(clock)
    rng = np.random.default_rng(0)
    for _ in range(2):
        parts, y = _chunk(rng)
        assert isinstance(svc.insert("t", parts, y), InsertReceipt)
    m_active = svc.state("t").tree.m_active
    led0 = svc.state("t").ledger.total
    # admission passes at t=0.6 < 1.0; the pre-reduce check lands past it
    q = svc.query("t", reduce_to=8, deadline=Deadline.after(clock, 1.0))
    assert isinstance(q, QueryReceipt)
    assert q.degraded and q.m == m_active and q.comm_delta == 0
    assert svc.state("t").ledger.total == led0      # union is free
    # an unpressed query still reduces
    q2 = svc.query("t", reduce_to=8)
    assert not q2.degraded and q2.m == 8 and q2.comm_delta > 0


def test_query_deadline_expired_at_admission_sheds():
    clock = SimClock(tick=0.0)
    svc = _svc(clock)
    rng = np.random.default_rng(0)
    parts, y = _chunk(rng)
    svc.insert("t", parts, y)
    r = svc.query("t", reduce_to=8, deadline=Deadline.after(clock, 0.0))
    assert isinstance(r, ShedReceipt) and r.reason == "deadline"


def test_rate_limited_tenant_sheds_and_recovers():
    clock = SimClock(tick=0.0)
    svc = CoresetService(clock=clock)
    svc.register("g", task="vrlr", budget=16, seed=0, block_size=BLOCK,
                 rate_limit=(1.0, 2))
    rng = np.random.default_rng(0)
    outs = [svc.insert("g", *_chunk(rng)) for _ in range(3)]
    assert [isinstance(o, InsertReceipt) for o in outs] == [True, True, False]
    assert outs[2].reason == "rate_limit" and outs[2].retry_after_s > 0
    clock.advance(2.0)                     # refill
    assert isinstance(svc.insert("g", *_chunk(rng)), InsertReceipt)


def test_global_inflight_cap_sheds_overloaded():
    svc = CoresetService(max_inflight=1)
    svc.register("t", task="vrlr", budget=16, seed=0, block_size=BLOCK)
    rng = np.random.default_rng(0)
    parts, y = _chunk(rng)
    svc._inflight = 1                      # a request is mid-flight
    r = svc.insert("t", parts, y)
    assert isinstance(r, ShedReceipt) and r.reason == "overloaded"
    svc._inflight = 0
    assert isinstance(svc.insert("t", parts, y), InsertReceipt)
    with pytest.raises(ValueError):
        CoresetService(max_inflight=0)


def test_submit_queue_bound_sheds_queue_full():
    rng = np.random.default_rng(0)
    svc = CoresetService()
    svc.register("t", task="vrlr", budget=16, seed=0, block_size=BLOCK,
                 max_pending=2)
    svc.attach_dataset("ref", _ds(rng))
    k = jax.random.PRNGKey(0)
    t1 = svc.submit("t", "ref", 8, key=k)
    t2 = svc.submit("t", "ref", 8, key=jax.random.fold_in(k, 1))
    assert isinstance(t1, int) and isinstance(t2, int)
    r = svc.submit("t", "ref", 8, key=jax.random.fold_in(k, 2))
    assert isinstance(r, ShedReceipt) and r.reason == "queue_full"
    svc.flush()                            # drains the queue
    assert isinstance(svc.submit("t", "ref", 8,
                                 key=jax.random.fold_in(k, 3)), int)


def test_breaker_trips_isolates_and_recovers_per_tenant():
    clock = SimClock(tick=0.5)
    svc = CoresetService(clock=clock)
    tr = Transport(FaultPlan(seed=3, drop=1.0, max_retries=1), clock=clock)
    svc.register("bad", task="vrlr", budget=16, seed=0, block_size=BLOCK,
                 fault_policy="retry", transport=tr,
                 breaker_threshold=2, breaker_cooldown_s=50.0)
    svc.register("good", task="vrlr", budget=16, seed=1, block_size=BLOCK)
    rng = np.random.default_rng(0)
    for _ in range(2):
        with pytest.raises(PartyUnavailable):
            svc.insert("bad", *_chunk(rng))
    br = svc.stats()["breakers"]["bad"]
    assert br["state"] == "open" and br["trips"] == 1
    assert "PartyUnavailable" in br["last_error"]
    shed = svc.insert("bad", *_chunk(rng))
    assert isinstance(shed, ShedReceipt) and shed.reason == "breaker_open"
    assert shed.retry_after_s > 0
    # the good tenant is untouched
    assert isinstance(svc.insert("good", *_chunk(rng)), InsertReceipt)
    assert svc.stats()["breakers"]["good"]["state"] == "closed"
    # cooldown passes; the transport still drops, so the probe reopens
    clock.advance(100.0)
    with pytest.raises(PartyUnavailable):
        svc.insert("bad", *_chunk(rng))
    assert svc.stats()["breakers"]["bad"]["trips"] == 2


def test_service_failover_receipt_and_draw_identity():
    rng = np.random.default_rng(0)
    chunks = [_chunk(np.random.default_rng(s)) for s in range(2)]

    def play(**extra):
        svc = CoresetService()
        svc.register("t", task="vrlr", budget=16, seed=5, block_size=BLOCK,
                     chunk_blocks=2, **extra)
        recs = [svc.insert("t", p, y) for p, y in chunks]
        return svc, recs, svc.query("t", reduce_to=16)

    svc_ok, recs_ok, q_ok = play()
    svc_fb, recs_fb, q_fb = play(failover=True, memory_budget_bytes=1)
    assert all(r.fallback == "pipelined->streamed" for r in recs_fb)
    assert all(r.stats.fallback == "pipelined->streamed" for r in recs_fb)
    assert all(r.fallback is None for r in recs_ok)
    np.testing.assert_array_equal(np.asarray(q_ok.result.indices),
                                  np.asarray(q_fb.result.indices))
    np.testing.assert_array_equal(np.asarray(q_ok.result.weights),
                                  np.asarray(q_fb.result.weights))
    assert svc_fb.state("t").ledger.total == svc_ok.state("t").ledger.total
    assert svc_fb.state("t").tree.fallbacks == 2
    assert svc_fb.state("t").tree.last_fallback == "pipelined->streamed"
    assert svc_fb.stats()["fallbacks"] == 2


def test_evict_drops_pending_submits():
    rng = np.random.default_rng(0)
    svc = CoresetService()
    svc.register("a", task="vrlr", budget=16, seed=0, block_size=BLOCK)
    svc.register("b", task="vrlr", budget=16, seed=1, block_size=BLOCK)
    svc.attach_dataset("ref", _ds(rng))
    k = jax.random.PRNGKey(0)
    svc.submit("a", "ref", 8, key=k)
    svc.submit("a", "ref", 8, key=jax.random.fold_in(k, 1))
    tb = svc.submit("b", "ref", 8, key=jax.random.fold_in(k, 2))
    ev = svc.evict("a")
    assert ev.dropped_pending == 2 and svc.pending == 1
    out = svc.flush()
    assert set(out) == {tb}                # a's tickets never execute


def test_flush_deadline_defers_unstarted_groups():
    rng = np.random.default_rng(0)
    clock = SimClock(tick=0.0)
    svc = CoresetService(clock=clock)
    svc.attach_dataset("ref", _ds(rng))
    k = jax.random.PRNGKey(0)
    t1 = svc.submit("x", "ref", 8, key=k)
    t2 = svc.submit("x", "ref", 12, key=jax.random.fold_in(k, 1))  # 2nd group
    out = svc.flush(deadline=Deadline.after(clock, 0.0))   # born expired
    assert out == {} and svc.pending == 2
    out = svc.flush()
    assert set(out) == {t1, t2}


# --------------------------------------------------------------------------
# PlanCache aging
# --------------------------------------------------------------------------

def test_plan_cache_prune_by_idle_age():
    t = [0.0]
    pc = PlanCache(time_fn=lambda: t[0])
    rng = np.random.default_rng(0)
    ds_a, ds_b = _ds(rng, n=256), _ds(rng, n=512)
    spec = CoresetSpec(task="vrlr", budgets=8, engine="streamed",
                       block_size=64)
    pc.get(spec, ds_a)
    t[0] = 10.0
    pc.get(spec, ds_b)
    t[0] = 15.0
    assert pc.prune(max_idle_s=8.0) == 1       # only ds_a is stale
    assert len(pc) == 1 and pc.evictions == 1
    s = pc.stats()
    assert s["oldest_idle_s"] == 5.0 and s["newest_idle_s"] == 5.0
    pc.get(spec, ds_b)                          # still cached
    assert pc.hits == 1
    pc.clear()
    assert len(pc) == 0 and pc.stats()["oldest_idle_s"] == 0.0
    with pytest.raises(ValueError):
        pc.prune(-1.0)


def test_service_exposes_plan_cache_maintenance():
    t = [0.0]
    svc = CoresetService(plan_cache=PlanCache(time_fn=lambda: t[0]))
    svc.register("t", task="vrlr", budget=16, seed=0, block_size=BLOCK)
    rng = np.random.default_rng(0)
    svc.insert("t", *_chunk(rng))
    assert svc.stats()["plan_cache_size"] == 1
    t[0] = 100.0
    assert svc.stats()["plan_oldest_idle_s"] == 100.0
    assert svc.prune_plans(50.0) == 1
    assert svc.stats()["plan_cache_size"] == 0
    svc.insert("t", *_chunk(rng))
    svc.clear_plans()
    assert svc.stats()["plan_cache_size"] == 0


# --------------------------------------------------------------------------
# Ledger/receipt reconciliation
# --------------------------------------------------------------------------

def _reconcile(seed, n_chunks, n_queries, budget=12):
    """One tenant's ledger total must equal the sum of comm units across
    its insert/query/flush receipts — no unattributed cost."""
    rng = np.random.default_rng(seed)
    svc = CoresetService()
    svc.register("t", task="vrlr", budget=budget, seed=seed, block_size=BLOCK)
    svc.attach_dataset("ref", _ds(rng))
    total = 0
    for i in range(n_chunks):
        r = svc.insert("t", *_chunk(rng, rows=200 + 50 * i))
        total += r.stats.comm_delta
        for _ in range(n_queries):
            q = svc.query("t", reduce_to=budget)
            total += q.comm_delta
    tk = svc.submit("t", "ref", 8, key=jax.random.PRNGKey(seed + 99))
    out = svc.flush()
    total += out[tk].comm_units
    assert svc.state("t").ledger.total == total
    return total


def test_ledger_receipt_reconciliation_fixed_seed():
    # deterministic pin: the composed bill for this exact workload
    total = _reconcile(0, n_chunks=3, n_queries=1)
    assert total == _reconcile(0, n_chunks=3, n_queries=1)
    assert total > 0


def test_ledger_receipt_reconciliation_property():
    # hypothesis sweep of the same invariant over (seed, workload shape):
    # whatever the mix of inserts/queries/submits, the tenant's ledger
    # total is exactly the sum of comm units across its receipts
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(seed=st.integers(min_value=0, max_value=2**16),
           n_chunks=st.integers(min_value=1, max_value=2),
           n_queries=st.integers(min_value=0, max_value=1))
    @settings(max_examples=6, deadline=None)
    def prop(seed, n_chunks, n_queries):
        _reconcile(seed, n_chunks=n_chunks, n_queries=n_queries)

    prop()
