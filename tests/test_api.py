"""Unified CoresetPipeline API: registry, pure DIS core, shims, batching.

Covers the api_redesign acceptance criteria:
  * task-registry round-trip;
  * `dis_plan` is bit-identical to a verbatim transcription of the seed's
    host-loop `dis_sample` for the same PRNG key;
  * the deprecated builder shims match `build_coreset` exactly, with the
    seed's exact ledger totals (and per-party round-2 attribution);
  * `jax.jit(dis_plan)` traces cleanly (no ledger side effects);
  * `build_coresets_batched` (vmap over seeds x budget grid) matches a
    Python loop of sequential builds;
  * `Coreset.materialize(ds, ledger)` accounts Theorem 2.5's +2mT term.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import (
    CORESET_TASKS,
    CommLedger,
    CommSchedule,
    VFLDataset,
    build_coreset,
    build_coresets_batched,
    get_task,
    theoretical_dis_cost,
)
from repro.core.api import CoresetTask, register_task
from repro.core.dis import dis_plan, dis_plan_full, server_plan
from repro.core.selector import sample_coreset


def _dataset(key, n=1200, d=12, T=3):
    kx, kt, kn = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n, d))
    theta = jax.random.normal(kt, (d,))
    y = X @ theta + 0.1 * jax.random.normal(kn, (n,))
    return VFLDataset.from_dense(X, y, T=T)


def _scores(key, n, T):
    keys = jax.random.split(key, T)
    return [jax.random.uniform(k, (n,)) + 1e-3 for k in keys]


def _seed_dis_sample(key, local_scores, m):
    """Verbatim transcription of the seed repo's host-loop dis_sample
    (ledger calls elided) — the bit-identity oracle."""
    scores = [jnp.asarray(g, jnp.float32) for g in local_scores]
    T = len(scores)
    G_j = jnp.stack([g.sum() for g in scores])
    G = G_j.sum()
    key, sub = jax.random.split(key)
    draws = jax.random.categorical(sub, jnp.log(jnp.maximum(G_j, 1e-30)), shape=(m,))
    a = jnp.bincount(draws, length=T)
    per = []
    for j in range(T):
        key, sub = jax.random.split(key)
        per.append(jax.random.categorical(
            sub, jnp.log(jnp.maximum(scores[j], 1e-30)), shape=(m,)))
    cand = jnp.stack(per)
    take = jnp.arange(m)[None, :] < a[:, None]
    order = jnp.argsort(~take.reshape(-1), stable=True)
    S = cand.reshape(-1)[order][:m]
    g_sum = jnp.zeros((m,), scores[0].dtype)
    for j in range(T):
        g_sum = g_sum + scores[j][S]
    w = G / (m * jnp.maximum(g_sum, 1e-30))
    return S, w, a


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

def test_registry_roundtrip():
    assert {"vrlr", "vkmc", "uniform"} <= set(CORESET_TASKS.keys())
    spec = get_task("vrlr")
    assert isinstance(spec, CoresetTask)
    assert spec.name == "vrlr" and spec.needs_labels
    assert get_task(spec) is spec                      # pass-through
    assert get_task("vkmc").deterministic_scores is False
    assert get_task("uniform").score_fn is None
    with pytest.raises(KeyError):
        get_task("no-such-task")


def test_registry_rejects_duplicates():
    with pytest.raises(KeyError):
        register_task("vrlr")(lambda key, ds, backend: None)


def test_unknown_backend_rejected():
    ds = _dataset(jax.random.PRNGKey(0), n=200)
    with pytest.raises(ValueError):
        build_coreset("vrlr", ds, 20, key=jax.random.PRNGKey(1), backend="bogus")


# --------------------------------------------------------------------------
# Pure DIS core: seed bit-identity + jit/vmap compatibility
# --------------------------------------------------------------------------

def test_dis_plan_bit_identical_to_seed_reference():
    for trial in range(5):
        n, T, m = 300 + 17 * trial, trial % 3 + 1, 64 + trial
        scores = _scores(jax.random.PRNGKey(100 + trial), n, T)
        key = jax.random.PRNGKey(trial)
        S0, w0, a0 = _seed_dis_sample(key, scores, m)
        plan = dis_plan_full(key, jnp.stack(scores), m)
        np.testing.assert_array_equal(np.asarray(S0), np.asarray(plan.indices))
        np.testing.assert_array_equal(np.asarray(w0), np.asarray(plan.weights))
        np.testing.assert_array_equal(np.asarray(a0), np.asarray(plan.counts))


def test_dis_plan_jits_cleanly():
    n, T, m = 400, 3, 50
    scores = jnp.stack(_scores(jax.random.PRNGKey(0), n, T))
    key = jax.random.PRNGKey(1)
    S_e, w_e = dis_plan(key, scores, m)
    S_j, w_j = jax.jit(dis_plan, static_argnums=2)(key, scores, m)
    np.testing.assert_array_equal(np.asarray(S_e), np.asarray(S_j))
    np.testing.assert_allclose(np.asarray(w_e), np.asarray(w_j), rtol=1e-6)


def test_dis_plan_vmaps_over_seeds():
    n, T, m = 250, 2, 40
    scores = jnp.stack(_scores(jax.random.PRNGKey(2), n, T))
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    Sv, wv = jax.vmap(lambda k: dis_plan(k, scores, m))(keys)
    assert Sv.shape == (5, m) and wv.shape == (5, m)
    for i, k in enumerate(keys):
        S_i, w_i = dis_plan(k, scores, m)
        np.testing.assert_array_equal(np.asarray(Sv[i]), np.asarray(S_i))


# --------------------------------------------------------------------------
# Shims: bit-identical (S, w), seed-exact ledger totals, fixed attribution
# --------------------------------------------------------------------------

def test_vrlr_shim_bit_identical_with_seed_ledger_total():
    ds = _dataset(jax.random.PRNGKey(4))
    m, T = 150, ds.T
    led_old, led_new = CommLedger(), CommLedger()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cs_old = core.build_vrlr_coreset(jax.random.PRNGKey(5), ds, m, ledger=led_old)
    cs_new = build_coreset("vrlr", ds, m, key=jax.random.PRNGKey(5), ledger=led_new)
    np.testing.assert_array_equal(np.asarray(cs_old.indices), np.asarray(cs_new.indices))
    np.testing.assert_array_equal(np.asarray(cs_old.weights), np.asarray(cs_new.weights))
    # the seed's exact bill: 2T (round 1) + m (round 2 up) + 2mT (bcast + round 3)
    assert led_old.total == led_new.total == 2 * T + m + 2 * m * T
    tags = led_new.by_tag()
    assert tags["dis/round1/G_j"] == T and tags["dis/round1/a_j"] == T
    assert tags["dis/round2/S_up"] == m
    assert tags["dis/round2/S_bcast"] == m * T
    assert tags["dis/round3/g_scores"] == m * T


def test_round2_upload_attributed_per_party():
    """The m index uploads are split across parties by the realised a_j —
    not lumped onto party 0 as in the seed."""
    ds = _dataset(jax.random.PRNGKey(6), n=2000)
    led = CommLedger()
    build_coreset("vrlr", ds, 300, key=jax.random.PRNGKey(7), ledger=led)
    ups = {msg.src: msg.units for msg in led.messages
           if msg.tag == "dis/round2/S_up"}
    assert sum(ups.values()) == 300
    # with n=2000 rows and near-even leverage mass, every party sends some
    assert all(u > 0 for u in ups.values()) and len(ups) == ds.T


def test_vkmc_shim_bit_identical():
    ds = _dataset(jax.random.PRNGKey(8))
    m, k = 120, 4
    led_old, led_new = CommLedger(), CommLedger()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cs_old = core.build_vkmc_coreset(jax.random.PRNGKey(9), ds, k=k, m=m,
                                         ledger=led_old)
    cs_new = build_coreset("vkmc", ds, m, key=jax.random.PRNGKey(9), k=k,
                           ledger=led_new)
    np.testing.assert_array_equal(np.asarray(cs_old.indices), np.asarray(cs_new.indices))
    np.testing.assert_array_equal(np.asarray(cs_old.weights), np.asarray(cs_new.weights))
    assert led_old.total == led_new.total


def test_uniform_shim_bit_identical():
    ds = _dataset(jax.random.PRNGKey(10))
    m = 80
    led = CommLedger()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cs_old = core.build_uniform_coreset(jax.random.PRNGKey(11), ds, m)
    cs_new = build_coreset("uniform", ds, m, key=jax.random.PRNGKey(11), ledger=led)
    np.testing.assert_array_equal(np.asarray(cs_old.indices), np.asarray(cs_new.indices))
    np.testing.assert_array_equal(np.asarray(cs_old.weights), np.asarray(cs_new.weights))
    assert led.total == m * ds.T                        # broadcast only


def test_build_coreset_requires_labels_for_vrlr():
    ds = _dataset(jax.random.PRNGKey(12), n=100)
    ds_unlabeled = VFLDataset(ds.parts, None)
    with pytest.raises(ValueError):
        build_coreset("vrlr", ds_unlabeled, 10, key=jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# Batched multi-seed / multi-budget construction
# --------------------------------------------------------------------------

def test_batched_vrlr_matches_python_loop_exactly():
    ds = _dataset(jax.random.PRNGKey(13))
    m = 100
    keys = jax.random.split(jax.random.PRNGKey(14), 4)
    grid = build_coresets_batched("vrlr", ds, [m], keys=keys, backend="ref")
    for r in range(4):
        seq = build_coreset("vrlr", ds, m, key=keys[r], backend="ref")
        cell = grid.coreset(r, 0)
        np.testing.assert_array_equal(np.asarray(cell.indices), np.asarray(seq.indices))
        np.testing.assert_array_equal(np.asarray(cell.weights), np.asarray(seq.weights))
        assert cell.comm_units == seq.comm_units


def test_batched_vkmc_matches_python_loop():
    ds = _dataset(jax.random.PRNGKey(15))
    m, k = 90, 4
    keys = jax.random.split(jax.random.PRNGKey(16), 3)
    grid = build_coresets_batched("vkmc", ds, [m], keys=keys, backend="ref", k=k)
    for r in range(3):
        seq = build_coreset("vkmc", ds, m, key=keys[r], backend="ref", k=k)
        cell = grid.coreset(r, 0)
        # indices exact; weights to float tolerance (vmapped k-means scoring
        # lowers with different reduction order than the sequential trace)
        np.testing.assert_array_equal(np.asarray(cell.indices), np.asarray(seq.indices))
        np.testing.assert_allclose(np.asarray(cell.weights), np.asarray(seq.weights),
                                   rtol=1e-5)


def test_batched_budget_grid_prefix_convention():
    ds = _dataset(jax.random.PRNGKey(17))
    ms = (40, 100)
    grid = build_coresets_batched("vrlr", ds, ms, key=jax.random.PRNGKey(18),
                                  num_seeds=2, backend="ref")
    assert grid.indices.shape == (2, 2, 100)
    # the tail beyond each budget is weight-0 padding
    assert float(jnp.sum(grid.weights[:, 0, 40:])) == 0.0
    for r in range(2):
        for mi, m in enumerate(ms):
            led = CommLedger()
            cs = grid.coreset(r, mi, ledger=led)
            assert cs.m == m
            assert bool(jnp.all(cs.weights > 0))
            assert led.total == 2 * ds.T + m + 2 * m * ds.T
            lo, hi = theoretical_dis_cost(m, ds.T)
            assert lo <= led.total <= hi


def test_batched_falls_back_when_deterministic_contract_broken():
    """A task flagged deterministic whose score_fn transforms the key must
    still produce batched cells identical to sequential builds (the builder
    detects the broken contract and scores per seed)."""
    ds = _dataset(jax.random.PRNGKey(25), n=400)

    def sneaky_scores(key, ds2, backend="ref"):
        key, sub = jax.random.split(key)                # consumes the key
        sc = jnp.stack([jnp.sum(p * p, axis=1) + 1.0 for p in ds2.parts])
        return sc, sub
    task = CoresetTask(name="sneaky", score_fn=sneaky_scores,
                       deterministic_scores=True)
    keys = jax.random.split(jax.random.PRNGKey(26), 3)
    grid = build_coresets_batched(task, ds, [25], keys=keys)
    for r in range(3):
        seq = build_coreset(task, ds, 25, key=keys[r], backend="ref")
        cell = grid.coreset(r, 0)
        # same dis_key => identical draws; weights to float tolerance only
        # (scores computed under vmap lower with a different reduction order)
        np.testing.assert_array_equal(np.asarray(cell.indices), np.asarray(seq.indices))
        np.testing.assert_allclose(np.asarray(cell.weights), np.asarray(seq.weights),
                                   rtol=1e-5)


def test_batched_rejects_zero_scores():
    ds = _dataset(jax.random.PRNGKey(27), n=60)

    def zero_scores(key, ds2, backend="ref"):
        return jnp.zeros((ds2.T, ds2.n)), key
    for deterministic in (True, False):
        task = CoresetTask(name="zero", score_fn=zero_scores,
                           deterministic_scores=deterministic)
        with pytest.raises(ValueError):
            build_coresets_batched(task, ds, [5], key=jax.random.PRNGKey(0),
                                   num_seeds=2)


def test_batched_accepts_typed_prng_keys():
    """New-style jax.random.key() keys work end to end (the deterministic
    contract check must not np.asarray a typed key)."""
    ds = _dataset(jax.random.PRNGKey(28), n=300)
    grid = build_coresets_batched("vrlr", ds, [20], key=jax.random.key(29),
                                  num_seeds=2)
    cs = grid.coreset(0, 0)
    assert cs.m == 20 and bool(jnp.all(cs.weights > 0))


def test_batched_uniform():
    ds = _dataset(jax.random.PRNGKey(19))
    grid = build_coresets_batched("uniform", ds, [30], key=jax.random.PRNGKey(20),
                                  num_seeds=2)
    cs = grid.coreset(0, 0)
    assert cs.m == 30 and cs.comm_units == 30 * ds.T
    np.testing.assert_allclose(np.asarray(cs.weights), ds.n / 30)


@pytest.mark.parametrize("transport", [False, True], ids=["recorded", "null-fault"])
def test_materialized_compiled_draw_matches_eager_core_row_chunked(
        monkeypatch, transport):
    """The materialized engine's compiled DIS core, with the round-2 draw
    forced into row chunks, equals an eager ``dis_plan_full`` on the same
    scores and key bit for bit, on the recorded path and through a
    null-fault transport, and equals the batched engine's m == m_cap cell."""
    from repro.core import CoresetPipeline, CoresetSpec, FaultPlan, Transport
    from repro.core import dis

    n, m = 1187, 41                                # a shape no other test draws
    monkeypatch.setattr(dis, "GUMBEL_CHUNK_BYTES", 4 * n * 6)   # 6-row chunks
    ds = _dataset(jax.random.PRNGKey(23), n=n)
    key = jax.random.PRNGKey(24)
    scores, dis_key = get_task("vrlr").score_fn(key, ds, backend="ref")
    eager = dis_plan_full(dis_key, scores, m)

    pipe = CoresetPipeline(ds)
    tr = Transport(FaultPlan.none()) if transport else None
    cs = pipe.build(pipe.plan(CoresetSpec(task="vrlr", budgets=m,
                                          engine="materialized", backend="ref")),
                    key=key, ledger=CommLedger(), transport=tr)
    np.testing.assert_array_equal(np.asarray(cs.indices), np.asarray(eager.indices))
    np.testing.assert_array_equal(np.asarray(cs.weights), np.asarray(eager.weights))

    cell = build_coresets_batched("vrlr", ds, [m], keys=key[None],
                                  backend="ref").coreset(0, 0)
    np.testing.assert_array_equal(np.asarray(cell.indices), np.asarray(cs.indices))
    np.testing.assert_array_equal(np.asarray(cell.weights), np.asarray(cs.weights))


# --------------------------------------------------------------------------
# Materialize accounting (Theorem 2.5's +2mT) and schedule composition
# --------------------------------------------------------------------------

def test_materialize_accounts_2mT():
    ds = _dataset(jax.random.PRNGKey(21))
    m, T = 60, ds.T
    led = CommLedger()
    cs = build_coreset("vrlr", ds, m, key=jax.random.PRNGKey(22), ledger=led)
    build_total = led.total
    XS, yS, w = cs.materialize(ds, led)
    assert XS.shape == (m, ds.d) and yS.shape == (m,) and w.shape == (m,)
    assert led.total == build_total + 2 * m * T
    # composition against the paper bounds: construction in [lo, hi], plus 2mT
    lo, hi = theoretical_dis_cost(m, T)
    assert lo + 2 * m * T <= led.total <= hi + 2 * m * T
    # ledger-less call unchanged
    XS2, _, _ = cs.materialize(ds)
    np.testing.assert_array_equal(np.asarray(XS), np.asarray(XS2))


def test_comm_schedule_validates_counts():
    with pytest.raises(ValueError):
        CommSchedule.dis(3, 10, counts=[5, 5, 5])       # sums to 15, not 10
    sched = CommSchedule.dis(3, 10, counts=[7, 3, 0])
    assert sched.total == 2 * 3 + 10 + 2 * 10 * 3
    led = CommLedger()
    sched.record(led)
    assert led.total == sched.total


# --------------------------------------------------------------------------
# Selector shares the DIS server core
# --------------------------------------------------------------------------

def test_selector_sampling_is_server_plan():
    g = jax.random.uniform(jax.random.PRNGKey(23), (64,)) + 1e-3
    key = jax.random.PRNGKey(24)
    S1, w1 = sample_coreset(key, g, 16)
    S2, w2 = server_plan(key, g, 16)
    np.testing.assert_array_equal(np.asarray(S1), np.asarray(S2))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
