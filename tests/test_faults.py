"""Party fault model: deterministic chaos plans, exact retry billing,
fault-free bit-identity pins for every engine + the tree, degraded builds,
checkpointed resume, crash-safe tree inserts, and the service's edge
validation.  (PR: fault-tolerant VFL rounds.)
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (
    CommLedger,
    Coreset,
    CoresetPipeline,
    CoresetSpec,
    FaultPlan,
    MaterializedCoreset,
    PartyUnavailable,
    PlanCache,
    StreamCheckpoint,
    Transport,
    VFLDataset,
    deliver_or_record,
)
from repro.core.comm import CommSchedule
from repro.serve import CoresetService, CoresetTree

BLOCK = 128


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    yield
    jax.clear_caches()


def _ds(seed=0, n=600, dims=(3, 2, 2), labels=True):
    rng = np.random.default_rng(seed)
    parts = [rng.normal(size=(n, d)).astype(np.float32) for d in dims]
    y = None
    if labels:
        theta = np.linspace(1.0, -1.0, dims[0]).astype(np.float32)
        y = (parts[0] @ theta
             + 0.1 * rng.normal(size=n).astype(np.float32))
    return VFLDataset(parts, y)


def _spec(engine="materialized", policy="fail", task="vrlr", m=32, **kw):
    params = {"k": 3} if task == "vkmc" else {}
    params.update(kw.pop("params", {}))
    return CoresetSpec(task=task, budgets=m, engine=engine, backend="ref",
                       fault_policy=policy, params=params,
                       block_size=BLOCK, **kw)


def _same_draw(a: Coreset, b: Coreset) -> bool:
    return (np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
            and np.array_equal(np.asarray(a.weights), np.asarray(b.weights)))


# -- FaultPlan: determinism + validation -------------------------------------


def test_fault_plan_decide_is_replayable():
    mk = lambda: FaultPlan(seed=3, drop=0.3, corrupt=0.1, delay=0.2)
    grid = [(f"dis/round{r}/x", j, a)
            for r in (1, 2, 3) for j in range(3) for a in range(4)]
    ev1 = [mk().decide(*g) for g in grid]
    ev2 = [mk().decide(*g) for g in grid]
    assert ev1 == ev2
    other = [FaultPlan(seed=4, drop=0.3, corrupt=0.1, delay=0.2).decide(*g)
             for g in grid]
    assert other != ev1  # the seed actually steers the draws
    statuses = {e.status for e in ev1}
    assert "ok" in statuses and statuses - {"ok"}  # some faults fired


def test_fault_plan_per_party_rates_and_null():
    plan = FaultPlan(seed=0, drop={1: 0.5})
    assert plan.rate("drop", 1) == 0.5
    assert plan.rate("drop", 0) == 0.0
    # party 0 has rate 0 -> always ok, no PRNG consulted
    assert all(plan.decide("t", 0, a).ok for a in range(8))
    assert FaultPlan.none().is_null
    assert not plan.is_null


@pytest.mark.parametrize("bad", [
    {"drop": 1.5}, {"corrupt": -0.1}, {"max_retries": -1},
    {"timeout_s": -1.0}, {"seed": "x"},
])
def test_fault_plan_validation(bad):
    with pytest.raises(ValueError):
        FaultPlan(**bad)


# -- Transport: billing exactness -------------------------------------------


def test_null_transport_bit_identical_to_record():
    sched = CommSchedule.dis(3, 16, counts=[10, 4, 2])
    led_rec, led_tr = CommLedger(), CommLedger()
    sched.record(led_rec)
    rep = Transport(FaultPlan.none()).deliver(sched, led_tr)
    assert [dataclasses.astuple(m) for m in led_tr.messages] == \
           [dataclasses.astuple(m) for m in led_rec.messages]
    assert rep.units == rep.units_base == sched.total
    assert rep.retries == 0 and not rep.failed


def test_deliver_or_record_without_transport_is_record():
    sched = CommSchedule.dis_round1(3)
    led = CommLedger()
    rep = deliver_or_record(sched, led, None)
    assert led.total == sched.total == rep.units
    assert rep.units_retried == 0


def test_retry_billing_base_tags_exact():
    sched = CommSchedule.dis(3, 16, counts=[16, 0, 0])
    plan = FaultPlan(seed=11, drop=0.35, max_retries=8)
    led = CommLedger()
    rep = Transport(plan).deliver(sched, led)
    retry_units = led.by_prefix("retry/")
    assert retry_units > 0  # chaos actually fired at this seed
    # base tags bill EXACTLY the fault-free schedule; retries are the rest
    assert led.total - retry_units == sched.total
    assert rep.units_base == sched.total
    assert rep.units_retried == retry_units
    assert rep.units == led.total


def test_exhaustion_raises_party_unavailable_with_attempt_count():
    sched = CommSchedule.dis_round1(3)
    plan = FaultPlan(seed=0, drop={1: 1.0}, max_retries=2)
    with pytest.raises(PartyUnavailable, match=r"party 1 unavailable: "
                                               r"3 attempt\(s\)") as ei:
        Transport(plan).deliver(sched, CommLedger())
    assert (ei.value.party, ei.value.attempts) == (1, 3)


def test_drop_on_exhaust_skips_the_partys_remaining_ops():
    sched = CommSchedule.dis(3, 12, counts=[4, 4, 4])
    plan = FaultPlan(seed=0, drop={1: 1.0}, max_retries=1)
    led = CommLedger()
    rep = Transport(plan).deliver(sched, led, drop_on_exhaust=True)
    assert set(rep.failed) == {1}
    assert rep.failed[1].attempts == 2
    # party 1 never lands a base-tag entry after its first exhaustion
    assert all("retry/" in m.tag for m in led.messages
               if "party:1" in (m.src, m.dst))


def test_transport_stats_accumulate_across_schedules():
    tr = Transport(FaultPlan(seed=2, drop=0.3, max_retries=6))
    for _ in range(3):
        tr.deliver(CommSchedule.dis_round1(4), CommLedger())
    s = tr.stats
    assert s.attempts == s.delivered + s.drops + s.corrupts + s.timeouts
    assert s.retries > 0 and s.sim_time_s > 0.0  # backoff accrued, not slept


# -- fault-free bit-identity: every engine + the tree ------------------------


@pytest.mark.parametrize("engine", ["materialized", "streamed", "pipelined"])
@pytest.mark.parametrize("task", ["vrlr", "vkmc", "uniform"])
def test_fault_free_transport_pins_bit_identical(engine, task):
    ds = _ds(labels=task == "vrlr")
    key = jax.random.PRNGKey(5)
    led0 = CommLedger()
    cs0 = CoresetPipeline(ds).build(_spec(engine, task=task), key=key,
                                    ledger=led0)
    for policy, plan in [("fail", FaultPlan.none()),
                         ("retry", FaultPlan(seed=9)),  # null rates
                         ("degrade", FaultPlan.none())]:
        led = CommLedger()
        cs = CoresetPipeline(ds).build(
            _spec(engine, policy, task=task), key=key, ledger=led,
            transport=Transport(plan))
        assert _same_draw(cs, cs0)
        assert cs.comm_units == cs0.comm_units
        assert cs.degraded is None
        assert [dataclasses.astuple(m) for m in led.messages] == \
               [dataclasses.astuple(m) for m in led0.messages]


def test_fault_free_tree_insert_pins_bit_identical():
    chunks = [_ds(seed=s, n=300) for s in range(3)]
    kw = dict(key=jax.random.PRNGKey(1), backend="ref", block_size=BLOCK)
    t0 = CoresetTree("vrlr", 48, **kw)
    t1 = CoresetTree("vrlr", 48, transport=Transport(FaultPlan.none()),
                     fault_policy="retry", **kw)
    for c in chunks:
        t0.insert([np.asarray(p) for p in c.parts], np.asarray(c.y))
        t1.insert([np.asarray(p) for p in c.parts], np.asarray(c.y))
    q0, q1 = t0.query(), t1.query()
    assert np.array_equal(q0.indices, q1.indices)
    assert np.array_equal(q0.weights, q1.weights)
    assert t0.ledger.total == t1.ledger.total
    assert [dataclasses.astuple(m) for m in t0.ledger.messages] == \
           [dataclasses.astuple(m) for m in t1.ledger.messages]


# -- chaos determinism: replay + fixed-seed pin ------------------------------


def _chaos_build(seed=123, drop=0.3):
    ds = _ds()
    tr = Transport(FaultPlan(seed=seed, drop=drop, max_retries=6))
    led = CommLedger()
    cs = CoresetPipeline(ds).build(_spec(policy="retry"),
                                   key=jax.random.PRNGKey(7),
                                   ledger=led, transport=tr)
    return cs, led, tr


def test_chaos_replay_identical():
    (cs1, led1, tr1), (cs2, led2, tr2) = _chaos_build(), _chaos_build()
    assert _same_draw(cs1, cs2)
    assert led1.by_tag() == led2.by_tag()
    assert tr1.stats.as_dict() == tr2.stats.as_dict()


def test_chaos_fixed_seed_pin():
    # pinned off plan seed 123 / drop 0.3: threefry is platform-stable, so
    # these exact numbers must reproduce anywhere (fault-free base is 230 =
    # dis_total(T=3, m=32); 2 drops -> 64 retry units on m-sized messages)
    cs, led, tr = _chaos_build()
    assert led.total == 294
    assert led.by_prefix("retry/") == 64
    assert tr.stats.retries == 2 and tr.stats.drops == 2
    assert cs.comm_units == led.total
    assert np.asarray(cs.indices)[:6].tolist() == [140, 576, 86, 101, 422, 206]


def test_chaos_replay_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    sched = CommSchedule.dis(3, 8, counts=[8, 0, 0])

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**16), drop=st.floats(0.0, 0.4),
           retries=st.integers(2, 5))
    def prop(seed, drop, retries):
        def deliver():
            plan = FaultPlan(seed=seed, drop=drop, max_retries=retries)
            led = CommLedger()
            rep = Transport(plan).deliver(sched, led, drop_on_exhaust=True)
            return rep, led
        rep1, led1 = deliver()
        rep2, led2 = deliver()
        assert rep1 == rep2
        assert led1.by_tag() == led2.by_tag()
        # exactness holds under ANY fault pattern: every surviving party's
        # base-tag bill equals its fault-free share
        dead = set(rep1.failed)
        for op in sched.ops:
            if op.party not in dead:
                assert led1.by_tag().get(op.tag, 0) >= op.units
    prop()


# -- degraded builds ---------------------------------------------------------


@pytest.mark.parametrize("engine", ["materialized", "streamed", "pipelined"])
def test_degrade_drops_party_and_issues_receipt(engine):
    ds = _ds()
    tr = Transport(FaultPlan(seed=0, drop={0: 1.0}, max_retries=2))
    led = CommLedger()
    cs = CoresetPipeline(ds).build(_spec(engine, policy="degrade"),
                                   key=jax.random.PRNGKey(3),
                                   ledger=led, transport=tr)
    d = cs.degraded
    assert d is not None
    assert d.surviving == (1, 2) and d.total_parties == 3
    assert d.dropped[0].party == 0
    assert d.bound_factor == pytest.approx(1.5)
    assert "2/3 parties survived" in d.describe()
    assert cs.comm_units == led.total
    assert np.asarray(cs.indices).max() < ds.n
    # the bill names only the parties that actually spoke in rounds 2-3
    assert all("party:0" not in (m.src, m.dst) for m in led.messages
               if m.tag.startswith("dis/round2"))


def test_degrade_label_party_loss_raises():
    ds = _ds()
    tr = Transport(FaultPlan(seed=0, drop={2: 1.0}, max_retries=1))
    with pytest.raises(PartyUnavailable):
        CoresetPipeline(ds).build(_spec(policy="degrade"),
                                  key=jax.random.PRNGKey(3), transport=tr)


def test_degrade_all_parties_lost_raises():
    ds = _ds(labels=False)
    tr = Transport(FaultPlan(seed=0, drop=1.0, max_retries=0))
    with pytest.raises(RuntimeError):
        CoresetPipeline(ds).build(_spec(policy="degrade", task="vkmc"),
                                  key=jax.random.PRNGKey(3), transport=tr)


def test_fail_and_retry_policies_raise_on_exhaustion():
    ds = _ds()
    for policy in ("fail", "retry"):
        tr = Transport(FaultPlan(seed=0, drop={1: 1.0}, max_retries=1))
        with pytest.raises(PartyUnavailable):
            CoresetPipeline(ds).build(_spec(policy=policy),
                                      key=jax.random.PRNGKey(3), transport=tr)


# -- spec / build validation -------------------------------------------------


def test_fault_policy_validation():
    with pytest.raises(ValueError, match="fault_policy must be one of"):
        _spec(policy="bogus")
    with pytest.raises(ValueError, match="batched engine bills its cells"):
        CoresetSpec(task="vrlr", budgets=(16,), engine="batched",
                    fault_policy="retry")
    assert "fault_policy=degrade" in CoresetPipeline(_ds()).plan(
        _spec(policy="degrade")).describe()


def test_build_rejects_incompatible_combinations():
    ds = _ds()
    pipe = CoresetPipeline(ds)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="batched engine bills its cells"):
        pipe.build(CoresetSpec(task="vrlr", budgets=(16,), engine="batched",
                               backend="ref"),
                   key=key, transport=Transport())
    with pytest.raises(ValueError, match="checkpointed resume is a "
                                         "streamed/pipelined-engine"):
        pipe.build(_spec("materialized"), key=key,
                   checkpoint=StreamCheckpoint())


# -- checkpointed resume -----------------------------------------------------


class _Bomb:
    def __init__(self, at):
        self.at, self.calls = at, 0

    def __call__(self):
        self.calls += 1
        if self.calls == self.at:
            raise RuntimeError("killed mid-scan")


@pytest.mark.parametrize("engine", ["streamed", "pipelined"])
@pytest.mark.parametrize("task", ["vrlr", "vkmc"])
def test_checkpoint_resume_draw_identical(engine, task):
    ds = _ds(n=700, labels=task == "vrlr")
    key = jax.random.PRNGKey(4)
    spec = _spec(engine, task=task, chunk_blocks=2)
    cs0 = CoresetPipeline(ds).build(spec, key=key)

    ck = StreamCheckpoint()
    with pytest.raises(RuntimeError, match="killed mid-scan"):
        CoresetPipeline(ds).build(spec, key=key, checkpoint=ck,
                                  probe=_Bomb(at=2))
    assert ck.saves > 0  # the crashed pass left resumable state behind
    cs1 = CoresetPipeline(ds).build(spec, key=key, checkpoint=ck)
    assert ck.resumes > 0
    assert _same_draw(cs1, cs0)
    assert cs1.comm_units == cs0.comm_units
    # a completed build clears its state: nothing stale for the next chunk
    assert ck.signature is None


def test_checkpoint_signature_mismatch_discards_stale_state():
    ds = _ds(n=700)
    spec = _spec("pipelined", chunk_blocks=2)
    ck = StreamCheckpoint()
    with pytest.raises(RuntimeError):
        CoresetPipeline(ds).build(spec, key=jax.random.PRNGKey(4),
                                  checkpoint=ck, probe=_Bomb(at=2))
    # resuming under a DIFFERENT key must not reuse key-4's accumulators
    other = jax.random.PRNGKey(8)
    cs = CoresetPipeline(ds).build(spec, key=other, checkpoint=ck)
    assert _same_draw(cs, CoresetPipeline(ds).build(spec, key=other))


# -- crash-safe tree inserts -------------------------------------------------


def _tree_chunks(num=4, rows=300):
    return [(_ds(seed=10 + s, n=rows).parts, _ds(seed=10 + s, n=rows).y)
            for s in range(num)]


def test_tree_crash_rolls_back_and_resumes_draw_identical():
    import repro.serve.tree as treemod

    chunks = [( [np.asarray(p) for p in parts], np.asarray(y) )
              for parts, y in _tree_chunks()]
    kw = dict(key=jax.random.PRNGKey(0), backend="ref",
              block_size=BLOCK, chunk_blocks=2)
    t_ref = CoresetTree("vrlr", 48, **kw)
    ck = StreamCheckpoint()
    t_cr = CoresetTree("vrlr", 48, checkpoint=ck, **kw)
    for i, (parts, y) in enumerate(chunks):
        t_ref.insert(parts, y)
        if i == 1:
            pre = (t_cr.ledger.total, t_cr.num_chunks, t_cr.n_total)
            orig = treemod.CoresetPipeline.build
            bomb = _Bomb(at=2)

            def crashing(self, *a, **kws):
                kws["probe"] = bomb
                return orig(self, *a, **kws)

            treemod.CoresetPipeline.build = crashing
            try:
                with pytest.raises(RuntimeError, match="killed mid-scan"):
                    t_cr.insert(parts, y)
            finally:
                treemod.CoresetPipeline.build = orig
            # the failed insert left NOTHING behind
            assert (t_cr.ledger.total, t_cr.num_chunks, t_cr.n_total) == pre
        t_cr.insert(parts, y)
    assert ck.resumes >= 1
    q_ref, q_cr = t_ref.query(), t_cr.query()
    assert np.array_equal(q_ref.indices, q_cr.indices)
    assert np.array_equal(q_ref.weights, q_cr.weights)
    assert t_ref.ledger.total == t_cr.ledger.total


# -- service edge validation + stats -----------------------------------------


def test_service_insert_validation():
    svc = CoresetService(backend="ref")
    svc.register("a", task="vrlr", budget=32, block_size=BLOCK)
    ds = _ds(n=200)
    with pytest.raises(ValueError, match="empty parts list"):
        svc.insert("a", [])
    with pytest.raises(ValueError, match="zero-row superchunk"):
        svc.insert("a", [np.zeros((0, 3)), np.zeros((0, 2))])
    with pytest.raises(ValueError, match="parties disagree on the chunk's "
                                         "row count"):
        svc.insert("a", [np.asarray(ds.parts[0]),
                         np.asarray(ds.parts[1])[:100]])
    # nothing above touched the tree
    assert svc.state("a").tree.num_chunks == 0


def test_service_chaos_tenant_streams_and_stats_expose_cache():
    svc = CoresetService(backend="ref",
                         plan_cache=PlanCache(max_entries=2))
    tr = Transport(FaultPlan(seed=6, drop=0.15, max_retries=6))
    svc.register("chaotic", task="vrlr", budget=32, block_size=BLOCK,
                 fault_policy="retry", transport=tr, checkpoint=True)
    for parts, y in _tree_chunks(num=2):
        svc.insert("chaotic", [np.asarray(p) for p in parts], np.asarray(y))
    rec = svc.query("chaotic")
    assert rec.m == 64  # two un-merged leaves of 32, concatenated
    assert rec.ledger_total == svc.state("chaotic").ledger.total
    s = svc.stats()
    for k in ("plan_cache_size", "plan_cache_max", "plan_hits",
              "plan_misses", "plan_evictions"):
        assert k in s
    assert s["plan_cache_max"] == 2


def test_vfl_dataset_rejects_degenerate_inputs():
    with pytest.raises(ValueError, match="at least one party"):
        VFLDataset([], None)
    with pytest.raises(ValueError, match=r"at least one row \(n=0\)"):
        VFLDataset([np.zeros((0, 3), np.float32)], None)


# -- PlanCache LRU bound -----------------------------------------------------


def test_plan_cache_lru_evicts_at_capacity():
    ds = _ds(n=200)
    pc = PlanCache(max_entries=2)
    specs = [_spec(m=8 + i) for i in range(3)]
    for sp in specs:
        pc.get(sp, ds)
    s = pc.stats()
    assert {k: s[k] for k in ("size", "max_entries", "hits", "misses",
                              "evictions")} == {
        "size": 2, "max_entries": 2, "hits": 0, "misses": 3, "evictions": 1}
    assert s["oldest_idle_s"] >= s["newest_idle_s"] >= 0.0
    pc.get(specs[2], ds)                  # newest entry: a hit
    assert pc.hits == 1
    pc.get(specs[0], ds)                  # evicted entry: a miss again
    assert pc.misses == 4
    with pytest.raises(ValueError, match="max_entries must be a positive"):
        PlanCache(max_entries=0)


# -- MaterializedCoreset edge cases ------------------------------------------


def _mat(seed, m=4, dims=(3, 2), offset=0, labels=True):
    ds = _ds(seed=seed, n=50, dims=dims, labels=labels)
    cs = Coreset(jax.numpy.arange(m), jax.numpy.ones(m), comm_units=7)
    return MaterializedCoreset.from_coreset(cs, ds, offset=offset)


def test_concat_edge_cases_pin_messages():
    with pytest.raises(ValueError, match="concat needs at least one coreset"):
        MaterializedCoreset.concat([])
    a, b = _mat(0), _mat(1, dims=(2, 3))
    with pytest.raises(ValueError, match=r"party widths differ across "
                                         r"coresets: coreset 0 has \(3, 2\), "
                                         r"coreset 1 has \(2, 3\)"):
        MaterializedCoreset.concat([a, b])
    with pytest.raises(ValueError, match="party counts differ"):
        MaterializedCoreset.concat([a, _mat(1, dims=(3, 2, 2))])
    with pytest.raises(ValueError, match="label presence differs"):
        MaterializedCoreset.concat([a, _mat(1, labels=False)])


def test_concat_with_empty_coreset_is_the_other_operand():
    full, empty = _mat(0, m=4), _mat(1, m=0)
    assert empty.m == 0
    u = MaterializedCoreset.concat([full, empty])
    assert u.m == 4
    assert np.array_equal(u.indices, full.indices)
    assert u.comm_units == full.comm_units + empty.comm_units


def test_from_coreset_offset_edges():
    ds = _ds(n=50, dims=(3, 2))
    cs = Coreset(jax.numpy.arange(4), jax.numpy.ones(4), comm_units=0)
    with pytest.raises(ValueError, match="offset must be >= 0, got -1"):
        MaterializedCoreset.from_coreset(cs, ds, offset=-1)
    with pytest.raises(OverflowError, match="global id overflow"):
        MaterializedCoreset.from_coreset(cs, ds,
                                         offset=np.iinfo(np.int64).max - 1)
    m = MaterializedCoreset.from_coreset(cs, ds, offset=100)
    assert m.indices.tolist() == [100, 101, 102, 103]
    # an empty coreset materializes to an m=0 node at any offset
    e = MaterializedCoreset.from_coreset(
        Coreset(jax.numpy.arange(0), jax.numpy.ones(0), comm_units=0),
        ds, offset=10)
    assert e.m == 0 and e.parts[0].shape == (0, 3)
