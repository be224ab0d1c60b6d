"""Pipelined streaming engine: draw-identity, superchunk-scan equivalence,
head-draw replay, and the sharded VKMC mass table.

The acceptance chain on top of ``tests/test_streaming.py``:

  1. ``blocks_prefetched`` / ``gather_blocks`` reproduce ``VFLDataset.block``
     contents exactly at every chunking (the staging layer is a layout
     change, not a data change);
  2. the superchunk-scan scorers (chunk_blocks > 1, prefetch on/off) build
     BIT-identical mass tables and per-block scores to the block-at-a-time
     scorers — the scan body is the same per-block computation in the same
     order (hypothesis property included);
  3. ``dis_plan_streamed_batched`` (grouped one-dispatch redraw, head-draw
     candidate replay) is bit-identical to PR 3's ``dis_plan_streamed``
     across odd nb, nb not divisible by chunk size, and the touched-block
     edge regimes (one touched block, all blocks touched, m=0);
  4. therefore ``build_coreset_streaming`` with the pipelined defaults
     matches the strict block-at-a-time engine draw for draw, ledger
     included — the pinned draw-identity acceptance;
  5. ``vkmc_block_masses_sharded`` (one stats psum + one mass psum) agrees
     with the streamed VKMC scorer's mass table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommLedger,
    VFLDataset,
    build_coreset,
    build_coreset_streaming,
)
from repro.core.streaming import (
    _categorical_head,
    _head_draws_ok,
    dis_plan_streamed,
    dis_plan_streamed_batched,
    make_stream_scorer,
    vkmc_block_masses_sharded,
    vkmc_local_centers,
)


def _dataset(key, n=1100, d=12, T=3):
    kx, kt, kn = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n, d))
    theta = jax.random.normal(kt, (d,))
    y = X @ theta + 0.1 * jax.random.normal(kn, (n,))
    return VFLDataset.from_dense(X, y, T=T)


def _assert_plans_equal(pa, pb):
    np.testing.assert_array_equal(np.asarray(pa.indices), np.asarray(pb.indices))
    np.testing.assert_array_equal(np.asarray(pa.weights), np.asarray(pb.weights))
    np.testing.assert_array_equal(np.asarray(pa.counts), np.asarray(pb.counts))
    np.testing.assert_array_equal(np.asarray(pa.totals), np.asarray(pb.totals))


# --------------------------------------------------------------------------
# 1: the staging layer is data-transparent
# --------------------------------------------------------------------------

@pytest.mark.parametrize("with_labels", [False, True])
@pytest.mark.parametrize("chunk_blocks,prefetch", [(1, True), (3, False),
                                                   (4, True), (64, True)])
def test_blocks_prefetched_matches_blocks(with_labels, chunk_blocks, prefetch):
    """Every (b, block) pair of the prefetched superchunk traversal equals
    VFLDataset.block(b) bitwise; zero-padded trailing blocks carry 0 valid
    rows and all-zero data."""
    ds = _dataset(jax.random.PRNGKey(0), n=505)
    bsz = 100
    nb, bs = ds.block_geometry(bsz)
    seen = 0
    for b0, chunk, nvalids in ds.blocks_prefetched(bsz, with_labels,
                                                   chunk_blocks, prefetch):
        for i in range(chunk.shape[0]):
            b = b0 + i
            if b >= nb:
                assert int(nvalids[i]) == 0
                assert float(jnp.abs(chunk[i]).sum()) == 0.0
                continue
            blk, nvalid = ds.block(b, bsz, with_labels)
            assert int(nvalids[i]) == nvalid
            np.testing.assert_array_equal(np.asarray(chunk[i]),
                                          np.asarray(blk))
            seen += 1
    assert seen == nb


def test_gather_blocks_matches_block():
    ds = _dataset(jax.random.PRNGKey(1), n=505)
    bsz = 100
    ids = [4, 0, 5, 2]            # out of order, includes the ragged tail
    batch, nvalids = ds.gather_blocks(ids, bsz, with_labels=True)
    for i, b in enumerate(ids):
        blk, nvalid = ds.block(b, bsz, with_labels=True)
        assert int(nvalids[i]) == nvalid
        np.testing.assert_array_equal(np.asarray(batch[i]), np.asarray(blk))
    with pytest.raises(IndexError):
        ds.gather_blocks([99], bsz, with_labels=True)


def test_numpy_backed_staging_matches_jnp():
    """The staging layer gives identical bits for numpy- and jnp-backed
    parts (numpy-backed is the zero-copy hot path)."""
    ds = _dataset(jax.random.PRNGKey(2), n=300)
    ds_np = VFLDataset([np.asarray(p) for p in ds.parts], np.asarray(ds.y))
    for (_, ca, _), (_, cb, _) in zip(
            ds.blocks_prefetched(64, True, 3, True),
            ds_np.blocks_prefetched(64, True, 3, True)):
        np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))


# --------------------------------------------------------------------------
# 2: superchunk-scan scorers == block-at-a-time scorers, bit for bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("task,params", [("vrlr", {}), ("vkmc", {"k": 4})])
@pytest.mark.parametrize("backend", ["ref", "norm"])
def test_chunked_scorer_masses_and_scores_bitwise(task, params, backend):
    ds = _dataset(jax.random.PRNGKey(3), n=1100)     # nb=9 at bs=128: odd nb
    key = jax.random.PRNGKey(4)
    legacy = make_stream_scorer(task, key, ds, 128, backend, **params)
    for C in (2, 4, 9, 50):                          # 9 % 2, 9 % 4 != 0
        for pf in (False, True):
            sc = make_stream_scorer(task, key, ds, 128, backend,
                                    chunk_blocks=C, prefetch=pf, **params)
            np.testing.assert_array_equal(np.asarray(legacy.masses),
                                          np.asarray(sc.masses))
    # the batched redraw scorer reproduces per-block scores bitwise
    sc = make_stream_scorer(task, key, ds, 128, backend, chunk_blocks=4,
                            prefetch=True, **params)
    batch = sc.score_blocks([8, 3, 0])               # includes ragged tail
    for i, b in enumerate([8, 3, 0]):
        np.testing.assert_array_equal(np.asarray(batch[i]),
                                      np.asarray(legacy.score_block(b)))


# --------------------------------------------------------------------------
# 3: the grouped one-dispatch redraw == PR 3's per-block redraw
# --------------------------------------------------------------------------

@pytest.mark.parametrize("task,params", [("vrlr", {}), ("vkmc", {"k": 4})])
def test_batched_redraw_draw_identity(task, params):
    """Across odd nb, nb not divisible by the chunk size, and several
    budgets, the grouped redraw reproduces dis_plan_streamed exactly."""
    ds = _dataset(jax.random.PRNGKey(5), n=1100)
    key = jax.random.PRNGKey(6)
    for bsz in (128, 333):
        legacy = make_stream_scorer(task, key, ds, bsz, "ref", **params)
        for m in (1, 17, 90):
            ref_plan = dis_plan_streamed(legacy, m)
            for C in (2, 3, 5):
                sc = make_stream_scorer(task, key, ds, bsz, "ref",
                                        chunk_blocks=C, prefetch=True,
                                        **params)
                _assert_plans_equal(ref_plan, dis_plan_streamed_batched(sc, m))


def test_batched_redraw_touched_block_edges():
    """nt edge cases: m=0 touches nothing, m=1 touches one block, a large
    budget touches every block (nt = nb)."""
    ds = _dataset(jax.random.PRNGKey(7), n=600)
    key = jax.random.PRNGKey(8)
    legacy = make_stream_scorer("vrlr", key, ds, 64, "ref")
    sc = make_stream_scorer("vrlr", key, ds, 64, "ref", chunk_blocks=4,
                            prefetch=True)
    nb = sc.nb
    # m = 0: empty plan, no dispatches
    p0_ref, p0 = dis_plan_streamed(legacy, 0), dis_plan_streamed_batched(sc, 0)
    assert p0.indices.shape == (0,) and p0.weights.shape == (0,)
    _assert_plans_equal(p0_ref, p0)
    # m = 1: exactly one touched block
    _assert_plans_equal(dis_plan_streamed(legacy, 1),
                        dis_plan_streamed_batched(sc, 1))
    # large m: every block is touched (checked, then identity)
    m = 3000
    plan = dis_plan_streamed_batched(sc, m)
    touched = {int(i) // sc.bs for i in np.asarray(plan.indices)}
    assert len(touched) == nb
    _assert_plans_equal(dis_plan_streamed(legacy, m), plan)


@pytest.mark.parametrize("partitionable", [True, False],
                         ids=["partitionable", "legacy"])
def test_head_draw_replay_matches_full_categorical(partitionable):
    """_categorical_head reproduces the first rows of the full-capacity
    categorical stream bit for bit across shapes, keys, and -inf padding,
    in both threefry layouts: any head up to cap in the partitionable one,
    heads up to cap // 2 in the legacy one."""
    with jax.threefry_partitionable(partitionable):
        for trial in range(8):
            k = jax.random.PRNGKey(100 + trial)
            bs = [4096, 128, 500, 64][trial % 4]
            cap = [512, 90, 34, 8][trial % 4]
            take = [5, 3, 16, 4][trial % 4]
            if partitionable and trial >= 4:
                take = [512, 61, 34, 7][trial % 4]    # past cap // 2
            take = take if partitionable else min(cap // 2, take)
            lg = jnp.log(jax.random.uniform(jax.random.fold_in(k, 1), (bs,))
                         + 1e-3).astype(jnp.float32)
            if trial % 2:                     # padded-row logits
                lg = jnp.where(jnp.arange(bs) < bs - 7, lg, -jnp.inf)
            assert _head_draws_ok(jnp.stack([k, k]), cap, bs, take)
            full = np.asarray(jax.random.categorical(k, lg, shape=(cap,)))
            head = np.asarray(_categorical_head(k, lg, cap, take))
            np.testing.assert_array_equal(full[:take], head)


def test_head_draws_gate():
    keys = jnp.stack([jax.random.PRNGKey(0)] * 3)
    with jax.threefry_partitionable(False):
        assert _head_draws_ok(keys, 512, 4096, 5)
        assert not _head_draws_ok(keys, 512, 4096, 300)    # take > cap // 2
        assert not _head_draws_ok(keys, 0, 4096, 0)        # empty capacity
        assert not _head_draws_ok(keys, 3, 7, 1)           # odd counter stream
    # the partitionable layout: the head is a plain prefix of the stream
    assert _head_draws_ok(keys, 512, 4096, 5)
    assert _head_draws_ok(keys, 512, 4096, 300)
    assert _head_draws_ok(keys, 512, 4096, 512)
    assert _head_draws_ok(keys, 3, 7, 1)
    assert not _head_draws_ok(keys, 512, 4096, 513)        # take > cap
    assert not _head_draws_ok(keys, 512, 4096, 0)          # empty head
    assert not _head_draws_ok(keys, 0, 4096, 0)            # empty capacity


# --------------------------------------------------------------------------
# 4: the entry point — pipelined defaults == strict block-at-a-time engine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("task,params", [("vrlr", {}), ("vkmc", {"k": 4})])
def test_build_streaming_pipelined_draw_identity(task, params):
    """THE acceptance pin: build_coreset_streaming with the pipelined
    defaults (chunked + prefetched) is draw-identical to the PR 3 engine
    (chunk_blocks=1, prefetch=False) — indices, weights, and the exact
    ledger bill."""
    ds = _dataset(jax.random.PRNGKey(9), n=1100)
    key = jax.random.PRNGKey(10)
    led_a, led_b = CommLedger(), CommLedger()
    cs_a = build_coreset_streaming(task, ds, 120, key=key, backend="ref",
                                   block_size=128, chunk_blocks=1,
                                   prefetch=False, ledger=led_a, **params)
    cs_b = build_coreset_streaming(task, ds, 120, key=key, backend="ref",
                                   block_size=128, ledger=led_b, **params)
    np.testing.assert_array_equal(np.asarray(cs_a.indices),
                                  np.asarray(cs_b.indices))
    np.testing.assert_array_equal(np.asarray(cs_a.weights),
                                  np.asarray(cs_b.weights))
    assert led_a.total == led_b.total == cs_b.comm_units


def test_build_streaming_pipelined_norm_flat_bit_identity():
    """block_size >= n + row-local scores: the PIPELINED path still matches
    the flat build_coreset bit for bit (the PR 3 contract survives)."""
    ds = _dataset(jax.random.PRNGKey(11))
    key = jax.random.PRNGKey(12)
    cs_f = build_coreset("vrlr", ds, 120, key=key, backend="norm")
    cs_s = build_coreset_streaming("vrlr", ds, 120, key=key, backend="norm",
                                   block_size=ds.n, chunk_blocks=4,
                                   prefetch=True)
    np.testing.assert_array_equal(np.asarray(cs_f.indices),
                                  np.asarray(cs_s.indices))
    np.testing.assert_array_equal(np.asarray(cs_f.weights),
                                  np.asarray(cs_s.weights))


def test_build_streaming_knob_validation():
    """block_size / chunk_blocks are validated HOST-side before any work;
    chunk_blocks above the block count clamps to one full-span superchunk."""
    ds = _dataset(jax.random.PRNGKey(13), n=400)
    key = jax.random.PRNGKey(0)
    for bad in (0, -1, 2.5, "64"):
        with pytest.raises(ValueError, match="block_size"):
            build_coreset_streaming("vrlr", ds, 10, key=key, block_size=bad)
    for bad in (0, -3, 1.5):
        with pytest.raises(ValueError, match="chunk_blocks"):
            build_coreset_streaming("vrlr", ds, 10, key=key, block_size=64,
                                    chunk_blocks=bad)
    # clamp: chunk_blocks > nb behaves as one superchunk over everything
    cs_a = build_coreset_streaming("vrlr", ds, 20, key=key, block_size=64,
                                   chunk_blocks=10_000)
    cs_b = build_coreset_streaming("vrlr", ds, 20, key=key, block_size=64,
                                   chunk_blocks=7)     # nb = ceil(400/64) = 7
    np.testing.assert_array_equal(np.asarray(cs_a.indices),
                                  np.asarray(cs_b.indices))


# --------------------------------------------------------------------------
# 5: sharded VKMC mass table
# --------------------------------------------------------------------------

def test_vkmc_sharded_masses_match_block_scan():
    from repro.launch.mesh import make_debug_mesh

    ds = _dataset(jax.random.PRNGKey(14), n=800)
    key = jax.random.PRNGKey(15)
    mesh = make_debug_mesh(n_data=1, n_model=1)
    ms = vkmc_block_masses_sharded(mesh, ds, 100, key=key, k=4)
    scorer = make_stream_scorer("vkmc", key, ds, 100, "ref", k=4)
    assert ms.shape == (ds.T, 8)
    np.testing.assert_allclose(np.asarray(ms), np.asarray(scorer.masses),
                               rtol=1e-4, atol=1e-6)


def test_vkmc_sharded_masses_rejects_misaligned_grid():
    from repro.launch.mesh import make_debug_mesh

    ds = _dataset(jax.random.PRNGKey(16), n=101)
    with pytest.raises(ValueError):
        vkmc_block_masses_sharded(make_debug_mesh(1, 1), ds, 100,
                                  key=jax.random.PRNGKey(0))


def test_vkmc_local_centers_key_chain_matches_scorer():
    """The centers helper consumes exactly the scorer's key chain, so the
    sharded table and the streamed scorer see the same local solutions and
    the same downstream DIS key."""
    ds = _dataset(jax.random.PRNGKey(17), n=300)
    key = jax.random.PRNGKey(18)
    centers, dis_key = vkmc_local_centers(key, ds, k=4)
    scorer = make_stream_scorer("vkmc", key, ds, 64, "ref", k=4)
    np.testing.assert_array_equal(np.asarray(dis_key),
                                  np.asarray(scorer.dis_key))


# --------------------------------------------------------------------------
# hypothesis: superchunk-scan == per-block composition, any geometry
# --------------------------------------------------------------------------

def test_property_superchunk_scan_equals_per_block():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(40, 400), st.integers(7, 64), st.integers(1, 9),
           st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=12, deadline=None)
    def prop(n, block_size, chunk_blocks, seed):
        ds = _dataset(jax.random.PRNGKey(seed), n=n, d=6, T=2)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
        legacy = make_stream_scorer("vrlr", key, ds, block_size, "ref")
        chunked = make_stream_scorer("vrlr", key, ds, block_size, "ref",
                                     chunk_blocks=chunk_blocks, prefetch=True)
        np.testing.assert_array_equal(np.asarray(legacy.masses),
                                      np.asarray(chunked.masses))
        m = max(1, n // 10)
        _assert_plans_equal(dis_plan_streamed(legacy, m),
                            dis_plan_streamed_batched(chunked, m))

    prop()
