"""CoresetSpec -> ExecutionPlan engine: spec validation, planner
boundaries, and forced-plan draw identity.

Covers the api_redesign acceptance criteria:
  * ALL knob validation is centralized in ``CoresetSpec.__post_init__``
    with uniform ValueError messages (block_size / chunk_blocks / budgets /
    engine / backend / memory_budget_bytes / m_cap);
  * the auto-planner flips materialized -> pipelined -> streamed EXACTLY at
    the memory-model thresholds, and records every decision (the
    ``chunk_blocks`` clamp is an explicit, described planner decision);
  * every forced plan is draw-identical to its legacy entry point (same key
    -> same indices, weights, and ledger totals) — the four legacy
    functions are thin shims over forced specs, and this pins it;
  * the predicted communication bill is EXACT (Algorithm 1's total is
    independent of the realised round-2 split);
  * the auto-plan smoke the CI step runs: two memory budgets -> two
    different engines, both draw-identical to their forced plans.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommLedger,
    CoresetPipeline,
    CoresetSpec,
    VFLDataset,
    build_coreset,
    build_coreset_streaming,
    build_coresets_batched,
    compile_plan,
)
from repro.core.plan import ENGINES, memory_model


def _dataset(key, n=1200, d=12, T=3, numpy_backed=False):
    kx, kt, kn = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n, d))
    theta = jax.random.normal(kt, (d,))
    y = X @ theta + 0.1 * jax.random.normal(kn, (n,))
    ds = VFLDataset.from_dense(X, y, T=T)
    if numpy_backed:
        ds = VFLDataset([np.asarray(p) for p in ds.parts], np.asarray(ds.y))
    return ds


def _same_coreset(a, b):
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
    np.testing.assert_array_equal(np.asarray(a.weights), np.asarray(b.weights))
    assert a.comm_units == b.comm_units


# --------------------------------------------------------------------------
# 1: centralized spec validation — uniform ValueError messages
# --------------------------------------------------------------------------

def test_spec_validates_budgets():
    for bad in (0, -1, (), (0, 20), (-3,), (1.5,), ("8",)):
        with pytest.raises(ValueError, match="budgets"):
            CoresetSpec(task="vrlr", budgets=bad)
    assert CoresetSpec(budgets=7).budgets == (7,)          # int normalizes
    assert CoresetSpec(budgets=[3, 9]).budgets == (3, 9)


def test_spec_validates_block_size_and_chunk_blocks():
    for bad in (0, -1, 2.5, "64", True):
        with pytest.raises(ValueError, match="block_size"):
            CoresetSpec(block_size=bad)
    for bad in (0, -3, 1.5, "4", True):
        with pytest.raises(ValueError, match="chunk_blocks"):
            CoresetSpec(chunk_blocks=bad)
    # None = planner default; ints pass through unclamped (the planner clamps)
    assert CoresetSpec(chunk_blocks=None).chunk_blocks is None
    assert CoresetSpec(chunk_blocks=10_000).chunk_blocks == 10_000


def test_spec_validates_enums_and_flags():
    with pytest.raises(ValueError, match="engine"):
        CoresetSpec(engine="warp")
    with pytest.raises(ValueError, match="backend"):
        CoresetSpec(backend="bogus")
    with pytest.raises(ValueError, match="num_seeds"):
        CoresetSpec(num_seeds=0)
    with pytest.raises(ValueError, match="prefetch"):
        CoresetSpec(prefetch=1)
    with pytest.raises(ValueError, match="memory_budget_bytes"):
        CoresetSpec(memory_budget_bytes=0)
    with pytest.raises(ValueError, match="sharded_masses"):
        CoresetSpec(sharded_masses=True, engine="materialized")
    with pytest.raises(ValueError, match="task"):
        CoresetSpec(task=42)


def test_spec_validates_m_cap():
    with pytest.raises(ValueError, match="m_cap"):
        CoresetSpec(m_cap=0)
    with pytest.raises(ValueError, match="budgets"):
        CoresetSpec(budgets=(10, 20), m_cap=15)
    spec = CoresetSpec(budgets=(10,), m_cap=16)
    assert spec.m_cap == 16


def test_spec_budget_property_and_grid():
    assert CoresetSpec(budgets=9).budget == 9
    grid = CoresetSpec(budgets=(3, 9), num_seeds=2)
    assert grid.is_grid
    with pytest.raises(ValueError, match="grid"):
        _ = grid.budget


def test_legacy_streaming_validation_via_spec():
    """The legacy entry point's knob validation now COMES FROM CoresetSpec
    — same matches, same host-side failure before any work."""
    ds = _dataset(jax.random.PRNGKey(0), n=400)
    key = jax.random.PRNGKey(1)
    for bad in (0, -1, 2.5, "64"):
        with pytest.raises(ValueError, match="block_size"):
            build_coreset_streaming("vrlr", ds, 10, key=key, block_size=bad)
    for bad in (0, -3, 1.5):
        with pytest.raises(ValueError, match="chunk_blocks"):
            build_coreset_streaming("vrlr", ds, 10, key=key, block_size=64,
                                    chunk_blocks=bad)
    with pytest.raises(ValueError, match="budgets"):
        build_coresets_batched("vrlr", ds, [], key=key)


# --------------------------------------------------------------------------
# 2: planner boundaries — engine flips exactly at the memory-model thresholds
# --------------------------------------------------------------------------

def _plan(ds, **spec_kw):
    return CoresetPipeline(ds).plan(CoresetSpec(task="vrlr", budgets=64,
                                                **spec_kw))


def test_auto_planner_threshold_flips():
    """materialized at >= its predicted bytes, pipelined one byte below,
    streamed one byte below the pipelined peak — the exact model values."""
    ds = _dataset(jax.random.PRNGKey(2), n=4096)
    kw = dict(block_size=256, chunk_blocks=2)
    mm = _plan(ds, **kw).memory_model
    assert mm["streamed"] < mm["pipelined"] < mm["materialized"]

    at = lambda B: _plan(ds, memory_budget_bytes=B, **kw)
    assert at(mm["materialized"]).engine == "materialized"
    assert at(mm["materialized"] - 1).engine == "pipelined"
    assert at(mm["pipelined"]).engine == "pipelined"
    p = at(mm["pipelined"] - 1)
    assert p.engine == "streamed" and not p.budget_exceeded
    assert at(mm["streamed"]).engine == "streamed"
    # below even the streamed floor: still streamed, flagged
    tight = at(mm["streamed"] - 1)
    assert tight.engine == "streamed" and tight.budget_exceeded
    assert "EXCEEDS" in tight.describe()


def test_planner_no_budget_defaults_materialized():
    ds = _dataset(jax.random.PRNGKey(3), n=500)
    plan = _plan(ds)
    assert plan.engine == "materialized"
    assert plan.predicted_peak_bytes == plan.memory_model["materialized"]


def test_planner_grid_forces_batched():
    ds = _dataset(jax.random.PRNGKey(4), n=300)
    pipeline = CoresetPipeline(ds)
    plan = pipeline.plan(CoresetSpec(task="vrlr", budgets=(10, 20),
                                     num_seeds=3))
    assert plan.engine == "batched" and plan.grid == (3, 2)
    with pytest.raises(ValueError, match="grid"):
        pipeline.plan(CoresetSpec(task="vrlr", budgets=(10, 20),
                                  engine="materialized"))


def test_planner_clamp_is_explicit_and_described():
    """chunk_blocks above the block count is a PLANNER decision: clamped,
    recorded in notes, and printed by describe()."""
    ds = _dataset(jax.random.PRNGKey(5), n=400)
    plan = CoresetPipeline(ds).plan(
        CoresetSpec(task="vrlr", budgets=20, engine="pipelined",
                    block_size=64, chunk_blocks=10_000))
    nb = -(-400 // 64)
    assert plan.chunk_blocks == nb
    assert any("clamped" in n for n in plan.notes)
    assert "clamped" in plan.describe()
    # legacy entry point behaves identically (clamp -> one full-span chunk)
    key = jax.random.PRNGKey(6)
    cs_a = build_coreset_streaming("vrlr", ds, 20, key=key, block_size=64,
                                   chunk_blocks=10_000)
    cs_b = build_coreset_streaming("vrlr", ds, 20, key=key, block_size=64,
                                   chunk_blocks=nb)
    _same_coreset(cs_a, cs_b)


def test_planner_lowers_degenerate_pipelined_to_streamed():
    ds = _dataset(jax.random.PRNGKey(7), n=400)
    plan = CoresetPipeline(ds).plan(
        CoresetSpec(task="vrlr", budgets=20, engine="pipelined",
                    block_size=64, chunk_blocks=1, prefetch=False))
    assert plan.engine == "streamed"
    assert any("lowered" in n for n in plan.notes)


def test_plan_predicted_comm_is_exact():
    """The DIS bill is independent of the realised a_j split, so the plan's
    prediction equals the realised ledger total for every engine."""
    ds = _dataset(jax.random.PRNGKey(8), n=600)
    pipeline = CoresetPipeline(ds)
    m = 40
    for engine in ("materialized", "streamed", "pipelined"):
        spec = CoresetSpec(task="vrlr", budgets=m, engine=engine,
                           block_size=128)
        plan = pipeline.plan(spec)
        led = CommLedger()
        cs = pipeline.build(plan, key=jax.random.PRNGKey(9), ledger=led)
        assert led.total == plan.predicted_comm_units == cs.comm_units
    # uniform: broadcast-only bill
    uplan = pipeline.plan(CoresetSpec(task="uniform", budgets=m))
    assert uplan.predicted_comm_units == m * ds.T


def test_memory_model_uniform_is_tiny():
    ds = _dataset(jax.random.PRNGKey(10), n=5000)
    plan = CoresetPipeline(ds).plan(
        CoresetSpec(task="uniform", budgets=16,
                    memory_budget_bytes=10_000))
    assert plan.engine == "materialized"        # nothing to stream
    assert plan.predicted_peak_bytes < 10_000


def test_memory_model_function_matches_plan():
    ds = _dataset(jax.random.PRNGKey(11), n=2048)
    plan = _plan(ds, block_size=256, chunk_blocks=4)
    mm = memory_model(plan.T, plan.n, plan.stacked_width, plan.bs,
                      4, 1, 1, plan.m_cap)
    for e in ENGINES:
        assert mm[e] == plan.memory_model[e]


# --------------------------------------------------------------------------
# 3: forced plans are draw-identical to the legacy entry points
# --------------------------------------------------------------------------

def test_forced_materialized_matches_build_coreset():
    ds = _dataset(jax.random.PRNGKey(12))
    key = jax.random.PRNGKey(13)
    pipeline = CoresetPipeline(ds)
    for task, params in (("vrlr", {}), ("vkmc", {"k": 4}), ("uniform", {})):
        led_a, led_b = CommLedger(), CommLedger()
        legacy = build_coreset(task, ds, 50, key=key, ledger=led_a, **params)
        spec = CoresetSpec(task=task, budgets=50, engine="materialized",
                           params=params)
        forced = pipeline.build(spec, key=key, ledger=led_b)
        _same_coreset(legacy, forced)
        assert led_a.total == led_b.total
        assert led_a.by_tag() == led_b.by_tag()


def test_forced_streaming_matches_build_coreset_streaming():
    ds = _dataset(jax.random.PRNGKey(16), n=1100, numpy_backed=True)
    key = jax.random.PRNGKey(17)
    pipeline = CoresetPipeline(ds)
    # pipelined: chunked + prefetched
    led_a, led_b = CommLedger(), CommLedger()
    legacy = build_coreset_streaming("vrlr", ds, 60, key=key, block_size=128,
                                     chunk_blocks=4, prefetch=True,
                                     ledger=led_a)
    forced = pipeline.build(
        CoresetSpec(task="vrlr", budgets=60, engine="pipelined",
                    block_size=128, chunk_blocks=4, prefetch=True),
        key=key, ledger=led_b)
    _same_coreset(legacy, forced)
    assert led_a.by_tag() == led_b.by_tag()
    # streamed: the block-at-a-time engine
    legacy_s = build_coreset_streaming("vrlr", ds, 60, key=key,
                                       block_size=128, chunk_blocks=1,
                                       prefetch=False)
    forced_s = pipeline.build(
        CoresetSpec(task="vrlr", budgets=60, engine="streamed",
                    block_size=128),
        key=key)
    _same_coreset(legacy_s, forced_s)
    # and the two streaming engines draw identically (the PR 4 invariant)
    _same_coreset(legacy, legacy_s)


def test_forced_batched_matches_build_coresets_batched():
    ds = _dataset(jax.random.PRNGKey(18), n=500)
    keys = jax.random.split(jax.random.PRNGKey(19), 3)
    legacy = build_coresets_batched("vrlr", ds, [10, 25], keys=keys)
    forced = CoresetPipeline(ds).build(
        CoresetSpec(task="vrlr", budgets=(10, 25), num_seeds=3,
                    engine="batched", backend="ref"),
        keys=keys)
    np.testing.assert_array_equal(np.asarray(legacy.indices),
                                  np.asarray(forced.indices))
    np.testing.assert_array_equal(np.asarray(legacy.weights),
                                  np.asarray(forced.weights))
    np.testing.assert_array_equal(np.asarray(legacy.counts),
                                  np.asarray(forced.counts))
    for r in range(3):
        for mi in range(2):
            _same_coreset(legacy.coreset(r, mi), forced.coreset(r, mi))


def test_pipeline_requires_key():
    ds = _dataset(jax.random.PRNGKey(20), n=200)
    pipeline = CoresetPipeline(ds)
    with pytest.raises(ValueError, match="key"):
        pipeline.build(CoresetSpec(task="vrlr", budgets=10))
    with pytest.raises(ValueError, match="key"):
        pipeline.build(CoresetSpec(task="vrlr", budgets=10, num_seeds=2))


def test_plan_requires_labels_eagerly():
    ds = _dataset(jax.random.PRNGKey(21), n=100)
    unlabeled = VFLDataset(ds.parts, None)
    with pytest.raises(ValueError, match="labels"):
        compile_plan(CoresetSpec(task="vrlr", budgets=10), unlabeled)


# --------------------------------------------------------------------------
# 4: the CI auto-plan smoke — two budgets, two engines, draws match forced
# --------------------------------------------------------------------------

def test_auto_plan_smoke_two_budgets():
    """Build via auto-plan at a loose and a tight memory budget: the chosen
    engines DIFFER, and each build is draw-identical to its forced plan."""
    ds = _dataset(jax.random.PRNGKey(22), n=8192, numpy_backed=True)
    key = jax.random.PRNGKey(23)
    pipeline = CoresetPipeline(ds)
    base = dict(task="vrlr", budgets=64, block_size=512, chunk_blocks=2)

    engines, draws = [], {}
    mm = pipeline.plan(CoresetSpec(**base)).memory_model
    for budget in (mm["materialized"], mm["streamed"]):
        spec = CoresetSpec(memory_budget_bytes=int(budget), **base)
        plan = pipeline.plan(spec)
        cs = pipeline.build(plan, key=key)
        forced = pipeline.build(
            CoresetSpec(engine=plan.engine, **base), key=key)
        _same_coreset(cs, forced)
        engines.append(plan.engine)
        draws[plan.engine] = cs
    assert engines[0] != engines[1]
    assert engines[0] == "materialized" and engines[1] == "streamed"


def test_auto_plan_streaming_engines_draw_identical():
    """When the auto-planner flips between streamed and pipelined, the
    draws do NOT change — engine selection is pure throughput/memory
    policy."""
    ds = _dataset(jax.random.PRNGKey(24), n=4096, numpy_backed=True)
    key = jax.random.PRNGKey(25)
    pipeline = CoresetPipeline(ds)
    base = dict(task="vrlr", budgets=48, block_size=256, chunk_blocks=2)
    mm = pipeline.plan(CoresetSpec(**base)).memory_model
    plan_p = pipeline.plan(
        CoresetSpec(memory_budget_bytes=int(mm["pipelined"]), **base))
    plan_s = pipeline.plan(
        CoresetSpec(memory_budget_bytes=int(mm["pipelined"]) - 1, **base))
    assert (plan_p.engine, plan_s.engine) == ("pipelined", "streamed")
    _same_coreset(pipeline.build(plan_p, key=key),
                  pipeline.build(plan_s, key=key))


# --------------------------------------------------------------------------
# 5: the sharded_masses plan toggle
# --------------------------------------------------------------------------

def test_sharded_masses_toggle_builds():
    # n divisible by (devices * block_size): the shard-grid requirement
    ds = _dataset(jax.random.PRNGKey(26), n=800)
    pipeline = CoresetPipeline(ds)
    spec = CoresetSpec(task="vrlr", budgets=40, engine="streamed",
                       block_size=100, sharded_masses=True)
    assert "sharded_masses" in pipeline.plan(spec).describe()
    cs = pipeline.build(spec, key=jax.random.PRNGKey(27))
    assert cs.m == 40 and bool(jnp.all(cs.weights > 0))
    # the sharded table is the scorer's table up to fp order, so the draws
    # match the unsharded engine whenever the tables agree bitwise — not
    # guaranteed; what IS guaranteed is a valid 40-sample DIS plan + bill
    assert cs.comm_units == pipeline.plan(spec).predicted_comm_units


def test_sharded_masses_rejects_norm_backend():
    ds = _dataset(jax.random.PRNGKey(28), n=800)
    spec = CoresetSpec(task="vrlr", budgets=10, engine="streamed",
                       block_size=100, sharded_masses=True, backend="norm")
    with pytest.raises(ValueError, match="sharded_masses"):
        CoresetPipeline(ds).build(spec, key=jax.random.PRNGKey(0))


def test_sharded_masses_misaligned_grid_fails_at_plan_time():
    """The shard-grid divisibility requirement is surfaced by the PLANNER,
    not deep inside the executor."""
    ds = _dataset(jax.random.PRNGKey(29), n=801)        # 801 % 100 != 0
    spec = CoresetSpec(task="vrlr", budgets=10, engine="streamed",
                       block_size=100, sharded_masses=True)
    with pytest.raises(ValueError, match="sharded_masses"):
        CoresetPipeline(ds).plan(spec)


# --------------------------------------------------------------------------
# 6: spec flags never dropped silently; stale plans rejected
# --------------------------------------------------------------------------

def test_auto_planner_never_drops_sharded_masses_silently():
    ds = _dataset(jax.random.PRNGKey(31), n=800)
    spec = CoresetSpec(task="vrlr", budgets=10, block_size=100,
                       sharded_masses=True)        # auto -> materialized
    with pytest.raises(ValueError, match="sharded_masses"):
        CoresetPipeline(ds).plan(spec)


def test_build_rejects_plan_from_other_dataset():
    ds_a = _dataset(jax.random.PRNGKey(32), n=400)
    ds_b = _dataset(jax.random.PRNGKey(33), n=800)
    plan = CoresetPipeline(ds_a).plan(CoresetSpec(task="vrlr", budgets=10))
    with pytest.raises(ValueError, match="recompile"):
        CoresetPipeline(ds_b).build(plan, key=jax.random.PRNGKey(0))
    # same (n, T) but different feature widths: the memory model and engine
    # selection are stale — must also be rejected
    ds_c = _dataset(jax.random.PRNGKey(33), n=400, d=24)
    with pytest.raises(ValueError, match="recompile"):
        CoresetPipeline(ds_c).build(plan, key=jax.random.PRNGKey(0))


def test_forced_streamed_has_no_clamp_note():
    """A forced-streamed plan ignores chunk_blocks (chunk = 1), so no
    contradictory clamp note appears in describe()."""
    ds = _dataset(jax.random.PRNGKey(34), n=400)
    plan = CoresetPipeline(ds).plan(
        CoresetSpec(task="vrlr", budgets=10, engine="streamed",
                    block_size=64, chunk_blocks=10_000))
    assert plan.chunk_blocks == 1
    assert not any("clamped" in n for n in plan.notes)
