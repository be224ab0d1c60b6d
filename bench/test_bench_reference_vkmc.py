"""The float64 reference of Algorithm 3 against the program, and a tiny
k-means cell run end to end on the CPU."""

from __future__ import annotations

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, harness, reference, reference_vkmc, work_kmeans
from bench.control import bf16_parties
from bench.drivers.kmeans_builds import standardize, weight_tie

BENCH = Path(__file__).resolve().parent
N, K, ITERS = 4096, 10, 4


@pytest.fixture(scope="module")
def parties():
    X, _ = data.year_msd(jax.random.PRNGKey(41), n=N, d=30)
    return [standardize(p) for p in data.split_parties(X, 3)]


def _program_path(key, X, k=K, iters=ITERS):
    from repro.core.vkmc import kmeans_plusplus, lloyd

    C = kmeans_plusplus(jnp.asarray(key), X, k)
    path = [C]
    for _ in range(iters):
        C = lloyd(X, C, iters=1, use_kernel=False)
        path.append(C)
    return np.asarray(jnp.stack(path), np.float64)


def test_seeding_replay_picks_the_programs_centres(parties):
    """Every D^2 draw of the program's ``kmeans_plusplus`` is the float64
    replay's best row (gap 0); under another key the picks are not."""
    subs = reference.key_chain(reference.raw_key(jax.random.PRNGKey(3)), 4)
    for j, X in enumerate(parties):
        from repro.core.vkmc import kmeans_plusplus

        C = np.asarray(kmeans_plusplus(jnp.asarray(subs[j]), X, K), np.float64)
        X64 = np.asarray(X, np.float64)
        gap, ties = reference_vkmc.seeding_gaps(subs[j], X64, C)
        assert gap == 0.0 and ties == 0
        wrong, _ = reference_vkmc.seeding_gaps(subs[(j + 1) % 3], X64, C)
        assert wrong > 1.0


def test_lloyd_steps_and_scores_match_the_program(parties):
    """Each one-iteration ``lloyd`` of the program is the float64 step from
    the centres before it to float32's summation error, and the float64
    Algorithm 3 scores at the program's final centres are ``vkmc_scores``'
    to 1e-5 relative (float32 distances in the expanded form and float32
    cluster sums over 4,096 rows)."""
    from repro.core import VFLDataset
    from repro.core.api import vkmc_scores

    key = jax.random.PRNGKey(9)
    got, _ = vkmc_scores(key, VFLDataset(parties, None), backend="ref", k=K,
                         alpha=2.0, local_iters=ITERS)
    subs = reference.key_chain(reference.raw_key(key), 4)
    for j, X in enumerate(parties):
        path = _program_path(subs[j], X)
        X64 = np.asarray(X, np.float64)
        assert reference_vkmc.lloyd_gap(X64, path) < 1e-5
        g, _, amb = reference_vkmc.scores(X64, path[-1], 2.0)
        assert amb.sum() <= 2
        np.testing.assert_allclose(np.asarray(got[j], np.float64)[~amb], g[~amb],
                                   rtol=1e-5)


def test_lloyd_gap_sees_a_step_from_other_data(parties):
    """The same step computed on the parties rounded to bfloat16 moves the
    centres far past float32's error."""
    X = parties[0]
    Xb = X.astype(jnp.bfloat16).astype(jnp.float32)
    sub = reference.key_chain(reference.raw_key(jax.random.PRNGKey(5)), 1)[0]
    path = _program_path(sub, Xb)
    assert reference_vkmc.lloyd_gap(np.asarray(X, np.float64), path) > 1e-3


def test_assignment_screen_equals_float64(parties):
    X = np.asarray(parties[1], np.float64)
    C = X[:K] + 1e-3
    x2 = np.einsum("nd,nd->n", X, X)
    a, b, amb = reference_vkmc._assign(X, jnp.asarray(X, jnp.float32), x2, C)
    a2, b2, _, _, amb2 = reference_vkmc._nearest_two(X, x2, C)
    np.testing.assert_array_equal(a, a2)
    np.testing.assert_array_equal(b, b2)
    np.testing.assert_array_equal(amb, amb2)


def test_work_counts_by_hand():
    # widths 3 and 2, k=4, n=10: 4 n k w flops; 4 (n w + 2 n) bytes a party
    assert work_kmeans.assign_update(10, [3, 2], 4) == (4 * 10 * 4 * 5.0,
                                                        4 * (10 * 5 + 2 * 10 * 2))
    cfg = {"task": "vkmc", "n": 10, "d": 5, "T": 2, "k": 4, "local_iters": 2}
    assert work_kmeans.lloyd_passes(cfg) == 3
    assert work_kmeans.build(cfg) == (3 * 800.0, 3 * 360.0)
    with pytest.raises(ValueError):
        work_kmeans.build(dict(cfg, task="vrlr"))


def _ctx(trace, completed=7, task="vkmc"):
    cfg = {"task": task, "n": 515345, "d": 90, "T": 3, "k": 10, "local_iters": 15}
    return types.SimpleNamespace(trace=trace, completed=completed, config=cfg,
                                 device={"kind": "TPU v5 lite"})


def test_lloyd_roofline_reads_the_window_kernel_time():
    """The least time of the builds traced over the window's kernel time:
    a kernel twice as slow reads half; no kernel event, no reading."""
    from bench import harness as h

    reader = h.load_reader("lloyd_roofline.build", BENCH.parent)
    flops, nbytes = work_kmeans.build(_ctx(None).config)
    from bench import peaks, work

    least, _ = work.least_seconds(flops, nbytes, peaks.peaks_for("TPU v5 lite"))
    fast = types.SimpleNamespace(kernel_events=112, kernel_sum_s=7 * least * 4)
    slow = types.SimpleNamespace(kernel_events=112, kernel_sum_s=7 * least * 8)
    assert reader(_ctx(fast)) == pytest.approx(25.0)
    assert reader(_ctx(slow)) == pytest.approx(12.5)
    assert reader(_ctx(types.SimpleNamespace(kernel_events=0, kernel_sum_s=0.0))) is None
    assert reader(_ctx(None)) is None
    assert reader(_ctx(fast, task="vrlr")) is None


def test_weight_tie_reads_the_weights_of_other_scores():
    g = np.array([[1.0, 2.0, 3.0, 4.0], [0.5, 0.5, 1.0, 1.0]])
    S = np.array([3, 0])
    w = g.sum() / (2 * g[:, S].sum(0))
    assert weight_tie(g, 2, S, w) == pytest.approx(0.0, abs=1e-15)
    assert weight_tie(g, 2, S, w * (1 + 3e-5)) == pytest.approx(3e-5)
    assert weight_tie(g, 2, np.array([3, 4]), w) == 1e9
    assert weight_tie(g, 3, S, w) == 1e9


# --------------------------------------------------------------------------
# A tiny k-means cell in the test's own checkout
# --------------------------------------------------------------------------

TINY_KMEANS = {"name": "tiny-kmeans", "task": "vkmc", "n": 3000, "d": 9, "T": 3,
               "k": 4, "alpha": 2.0, "local_iters": 3, "m": 32, "precision": "float32",
               "check": {"builds": 2}}


@pytest.fixture
def kmeans_root(tiny_root):
    """``tiny_root`` with a ``kmeans.mat`` cell of the k-means driver, its
    limits the deployment's, and the k-means readers."""
    with open(BENCH / "configs" / "yearmsd-kmeans.json") as f:
        limits = json.load(f)["limits"]
    (tiny_root / "bench" / "configs" / "tiny-kmeans.json").write_text(
        json.dumps(dict(TINY_KMEANS, limits=limits)))
    (tiny_root / "bench" / "traffic" / "tiny-kmeans-mat.json").write_text(json.dumps(
        {"driver": "kmeans_builds", "engine": "materialized", "resident": "device"}))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-kmeans", "source": "test",
                             "file": "bench/configs/tiny-kmeans.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "kmeans.mat", "config": "tiny-kmeans",
                               "traffic": "tiny-kmeans-mat", "chips": 1, "why": "test"})
    for name, unit, src, layer in [("seed_s.build", "s", "host_clock", "local k-means"),
                                   ("idle_score.build", "%", "device_trace",
                                    "local k-means"),
                                   ("lloyd_roofline.build", "%", "device_trace",
                                    "score kernels")]:
        bench["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                   "source": src, "layer": layer, "moves": "build_s",
                                   "workloads": ["kmeans.mat"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    return tiny_root


def test_tiny_kmeans_cell_runs_end_to_end(kmeans_root, run_cell):
    res, _ = run_cell("kmeans.mat")
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"bill_units", "draw_gap", "weight_rel", "lloyd_gap",
                                  "lloyd_tie", "weight_tie"}
    assert res["checks"]["lloyd_tie"]["value"] == 0.0
    assert set(res["metrics"]) == {"setup_s", "build_s"}


def test_tiny_kmeans_traced_run_reads_the_program_spans(kmeans_root, run_cell):
    """On the CPU there is no device plane: the span readers read, the
    kernel roofline finds no kernel event and is left out."""
    res, _ = run_cell("kmeans.mat", trace=True)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["seed_s.build"]["value"] > 0
    assert "idle_score.build" in res["metrics"]
    assert "lloyd_roofline.build" not in res["metrics"]


def test_tiny_kmeans_check_fails_on_bf16_parties(kmeans_root, run_cell):
    res, _ = run_cell("kmeans.mat", hook=bf16_parties)
    assert res["correct"] is False
    failed = {k for k, v in res["checks"].items() if v["value"] > v["limit"]}
    assert "lloyd_gap" in failed


def _drop_one_iteration(lloyd):
    return lambda X, C, iters=25, **kw: lloyd(X, C, iters=iters - 1, **kw)


def _bf16_lloyd(lloyd):
    return lambda X, C, **kw: lloyd(X.astype(jnp.bfloat16).astype(jnp.float32), C, **kw)


@pytest.mark.parametrize("fault", [_drop_one_iteration, _bf16_lloyd])
def test_tiny_kmeans_check_fails_on_a_fault_in_the_timed_lloyd_call(
        kmeans_root, run_cell, monkeypatch, fault):
    """A fault in the ``lloyd`` call of ``vkmc_scores`` alone, the timed
    path, while the check's own steps run the program's ``lloyd`` as it is:
    the timed build's weights are not those of the checked centres."""
    from repro.core import api
    from repro.core.vkmc import lloyd

    monkeypatch.setattr(api, "lloyd", fault(lloyd))
    res, _ = run_cell("kmeans.mat")
    assert res["correct"] is False
    failed = {k for k, v in res["checks"].items() if v["value"] > v["limit"]}
    assert "weight_tie" in failed, res["checks"]


def test_tiny_kmeans_check_fails_under_a_wrong_build_key(kmeans_root):
    Driver = harness.load_driver({"driver": "kmeans_builds"}, kmeans_root)
    cell = harness.load_cell("kmeans.mat", kmeans_root)
    d = Driver(jax, cell.config, cell.traffic, 11, harness.Spans(jax, False))
    d.setup()
    d.window(0.0)
    d.free()
    (i, paths, _), = d._check[:1]
    S, w = d._outs[i]
    b = d.built[i]
    args = (cell.config["alpha"], cell.config["m"], cell.config["n"], S, w,
            b.party_counts, paths)
    good = reference_vkmc.check_build(reference.raw_key(b.key), d._parts64, *args)
    bad = reference_vkmc.check_build(reference.raw_key(b.key) ^ np.uint32(1),
                                     d._parts64, *args)
    lim = cell.config["limits"]
    assert good["draw_gap"] <= lim["draw_gap"] and good["weight_rel"] <= lim["weight_rel"]
    assert bad["draw_gap"] > lim["draw_gap"]


def test_kmeans_driver_refuses_a_task_it_cannot_check(tiny_root):
    Driver = harness.load_driver({"driver": "kmeans_builds"}, tiny_root)
    cfg = {"task": "vrlr", "n": 3000, "d": 9, "T": 3, "m": 32}
    d = Driver(jax, cfg, {"engine": "materialized", "resident": "device"}, 1,
               harness.Spans(jax, False))
    with pytest.raises(ValueError, match="vkmc"):
        d.setup()
