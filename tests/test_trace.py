"""Host spans of the coreset build (``repro.utils.trace``): nothing without
a profiler session, the documented span tree under one, compiles marked
inside the span that caused them, and draws unchanged by tracing."""

from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import CommLedger, CoresetPipeline, CoresetSpec, VFLDataset
from repro.utils import trace

N = 3000


def _data(host: bool):
    X = jax.random.normal(jax.random.PRNGKey(0), (N, 9))
    y = X @ jnp.arange(1.0, 10.0) + 0.1
    parts = [X[:, 0:3], X[:, 3:6], X[:, 6:9]]
    if host:
        return VFLDataset([np.asarray(p) for p in parts], np.asarray(y))
    return VFLDataset(parts, y)


def _build(engine: str, k):
    ds = _data(host=engine != "materialized")
    spec = CoresetSpec(task="vrlr", budgets=32, engine=engine, backend="ref",
                       block_size=1024, chunk_blocks=2)
    pipe = CoresetPipeline(ds)
    led = CommLedger()
    cs = pipe.build(pipe.plan(spec), key=k, ledger=led)
    return np.asarray(cs.indices), np.asarray(cs.weights), led.total


def _spans(trace_dir):
    """(name, start, end, stats) of every ``repro.*`` event, by thread."""
    path = sorted(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("repro.")]
            if evs:
                out.append(evs)
    return out


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def _children(thread, parent, name):
    return [e for e in thread if e[0] == name and _inside(e, parent)]


def test_span_without_a_session_is_the_shared_null_context(monkeypatch):
    registered = []
    monkeypatch.setattr(trace, "_listening", False)
    monkeypatch.setattr(jax.monitoring, "register_event_duration_secs_listener",
                        registered.append)
    a = trace.span("build", build=1, engine="materialized")
    b = trace.span("stage")
    assert a is b is trace._NULL
    with a:
        trace.add(bytes=10)          # no open span: nothing kept
    assert registered == []
    assert trace._open.spans == []


def test_materialized_build_span_tree(tmp_path):
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    _build("materialized", keys[0])                       # warm
    with jax.profiler.trace(str(tmp_path)):
        _build("materialized", keys[1])
    (thread,) = _spans(tmp_path)
    builds = [e for e in thread if e[0] == "repro.build"]
    assert len(builds) == 1
    b = builds[0]
    assert b[3]["engine"] == "materialized" and b[3]["build"] >= 1
    for name in ("repro.score", "repro.dis", "repro.health", "repro.bill"):
        assert len(_children(thread, b, name)) == 1, name
    waits = _children(thread, b, "repro.wait")
    assert sorted(w[3]["of"] for w in waits) == ["counts", "totals"]
    plans = [e for e in thread if e[0] == "repro.plan"]
    assert len(plans) == 1 and plans[0][2] <= b[1]


def test_vkmc_materialized_score_spans(tmp_path):
    """Algorithm 3's scoring opens ``score.seed``, ``.lloyd`` and ``.sens``
    inside the engine's ``repro.score``, and counts the fused Lloyd passes
    over X there: T x (local_iters + 1)."""
    ds = _data(host=False)
    ds = VFLDataset(ds.parts, None)
    spec = CoresetSpec(task="vkmc", budgets=32, engine="materialized", backend="ref",
                       params={"k": 4, "local_iters": 3})
    pipe = CoresetPipeline(ds)
    keys = jax.random.split(jax.random.PRNGKey(8), 2)
    pipe.build(pipe.plan(spec), key=keys[0]).indices.block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        pipe.build(pipe.plan(spec), key=keys[1]).indices.block_until_ready()
    (thread,) = _spans(tmp_path)
    (b,) = [e for e in thread if e[0] == "repro.build"]
    (score,) = _children(thread, b, "repro.score")
    assert score[3]["lloyd_passes"] == ds.T * (3 + 1)
    got = [e for e in thread if e[0].startswith("repro.score.")]
    assert [e[0] for e in sorted(got, key=lambda e: e[1])] == [
        "repro.score.seed", "repro.score.lloyd", "repro.score.sens"]
    assert all(_inside(e, score) for e in got)


def test_pipelined_build_span_tree(tmp_path):
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    _build("pipelined", keys[0])
    with jax.profiler.trace(str(tmp_path)):
        _build("pipelined", keys[1])
    (thread,) = _spans(tmp_path)
    (b,) = [e for e in thread if e[0] == "repro.build"]
    assert b[3]["engine"] == "pipelined"
    (score,) = _children(thread, b, "repro.score")
    for name in ("repro.score.gram", "repro.score.pinv", "repro.score.mass"):
        assert len(_children(thread, score, name)) == 1, name
    stages = _children(thread, b, "repro.stage")
    # 3 blocks of 1024 rows in superchunks of 2: two per scan pass, plus the
    # redraw's gathers
    gram = _children(thread, b, "repro.score.gram")[0]
    assert len(_children(thread, gram, "repro.stage")) == 2
    assert len(stages) >= 5
    assert all(s[3]["bytes"] > 0 for s in stages)
    assert _children(thread, gram, "repro.stage")[0][3]["bytes"] == 2 * 3 * 1024 * 4 * 4
    (dis,) = _children(thread, b, "repro.dis")
    assert {w[3]["of"] for w in _children(thread, dis, "repro.wait")} == {"draws", "rows"}
    for name in ("repro.health", "repro.bill"):
        assert len(_children(thread, b, name)) == 1, name


def _pipelined_rows(indices, counts, m, nb, bs, chunk_blocks):
    """Rows the grouped redraw computes from a realised plan: per group of
    ``chunk_blocks`` touched blocks, its occupied cells padded to a multiple
    of 8 times its longest head, bucketed to a power of two up to m."""
    a_cells = {}
    for j, rows in enumerate(np.split(indices, np.cumsum(counts)[:-1])):
        for b in rows // bs:
            a_cells[(j, int(b))] = a_cells.get((j, int(b)), 0) + 1
    touched = sorted({b for _, b in a_cells})
    total = 0
    for g0 in range(0, len(touched), chunk_blocks):
        group = set(touched[g0:g0 + chunk_blocks])
        heads = [a for (j, b), a in a_cells.items() if b in group]
        take = max(heads)
        pow2 = 1 << (take - 1).bit_length()
        total += -(-len(heads) // 8) * 8 * (pow2 if pow2 <= m else take)
    return total


@pytest.mark.parametrize("engine", ["materialized", "pipelined"])
def test_dis_span_counts_the_round2_rows(engine, tmp_path, monkeypatch):
    """``draw_rows`` on ``repro.dis`` is the candidate rows round 2
    computed: sum_j ceil(a_j / rows) * rows on the materialized engine, at
    most (ceil(m / rows) + T - 1) * rows against T * m whole-stream rows;
    the grouped head draws' cells times their head on the pipelined one."""
    from repro.core import dis

    m, rows = 32, 5
    monkeypatch.setattr(dis, "GUMBEL_CHUNK_BYTES", 4 * N * rows)
    ds = _data(host=engine != "materialized")
    spec = CoresetSpec(task="vrlr", budgets=m, engine=engine, backend="ref",
                       block_size=1024, chunk_blocks=2)
    pipe = CoresetPipeline(ds)
    keys = jax.random.split(jax.random.PRNGKey(14), 2)
    pipe.build(pipe.plan(spec), key=keys[0])                 # warm
    led = CommLedger()
    with jax.profiler.trace(str(tmp_path)):
        cs = pipe.build(pipe.plan(spec), key=keys[1], ledger=led)
        indices = np.asarray(cs.indices)
    counts = [msg.units for msg in led.messages
              if msg.tag == "dis/round2/S_up"]
    assert len(counts) == ds.T and sum(counts) == m
    (thread,) = _spans(tmp_path)
    (d,) = [e for e in thread if e[0] == "repro.dis"]
    if engine == "materialized":
        want = sum(-(-a // rows) for a in counts) * rows
        assert d[3]["draw_rows"] == want
        assert want <= (-(-m // rows) + ds.T - 1) * rows < ds.T * m
    else:
        assert d[3]["draw_rows"] == _pipelined_rows(indices, counts, m,
                                                    nb=3, bs=1024,
                                                    chunk_blocks=2)


def test_forced_recompile_is_marked_inside_its_span(tmp_path):
    f = jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("probe"):
            f(jnp.ones((37, 5))).block_until_ready()      # a shape never seen
    (thread,) = _spans(tmp_path)
    (probe,) = [e for e in thread if e[0] == "repro.probe"]
    marks = [e for e in thread if e[0] == "repro.compile"]
    assert marks and all(_inside(m, probe) for m in marks)
    assert probe[3]["compiles"] == len(marks)
    assert probe[3]["compile_s"] == pytest.approx(sum(m[3]["secs"] for m in marks))
    end = marks[-1][1]
    assert probe[1] <= end - marks[-1][3]["secs"] * 1e9


def test_warm_materialized_build_compiles_nothing(tmp_path):
    """The materialized engine's DIS core is traced once per shape: a second
    build of the same shapes compiles nothing and its ``repro.dis`` carries
    no ``dis_traces``; a build at a new ``m`` traces the core once."""
    X = jax.random.normal(jax.random.PRNGKey(5), (2311, 7))
    ds = VFLDataset([X[:, 0:2], X[:, 2:4], X[:, 4:7]], X @ jnp.arange(7.0))
    pipe = CoresetPipeline(ds)
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    compiles = []

    def on_duration(event, secs, **_):
        if event == trace.COMPILE_EVENT:
            compiles.append(secs)

    def build(k, m, where):
        spec = CoresetSpec(task="vrlr", budgets=m, engine="materialized",
                           backend="ref")
        with jax.profiler.trace(str(where)):
            pipe.build(pipe.plan(spec), key=k).indices.block_until_ready()
        (thread,) = _spans(where)
        (dis,) = [e for e in thread if e[0] == "repro.dis"]
        return dis[3]

    assert build(keys[0], 29, tmp_path / "cold").get("dis_traces") == 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        warm = build(keys[1], 29, tmp_path / "warm")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert "dis_traces" not in warm and "compiles" not in warm
    assert build(keys[2], 30, tmp_path / "new_m").get("dis_traces") == 1


def test_warm_vkmc_seeding_compiles_nothing(tmp_path):
    """Algorithm 3's k-means++ seeding is compiled once per shape and ``k``:
    a second build of the same shapes compiles nothing and its
    ``repro.score.seed`` carries no ``compiles``; a build at a new ``k``
    compiles the seeding again."""
    X = jax.random.normal(jax.random.PRNGKey(12), (2203, 8))
    ds = VFLDataset([X[:, 0:3], X[:, 3:5], X[:, 5:8]], None)
    pipe = CoresetPipeline(ds)
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    compiles = []

    def on_duration(event, secs, **_):
        if event == trace.COMPILE_EVENT:
            compiles.append(secs)

    def build(key, k, where):
        spec = CoresetSpec(task="vkmc", budgets=19, engine="materialized",
                           backend="ref", params={"k": k, "local_iters": 2})
        with jax.profiler.trace(str(where)):
            pipe.build(pipe.plan(spec), key=key).indices.block_until_ready()
        (thread,) = _spans(where)
        (seed,) = [e for e in thread if e[0] == "repro.score.seed"]
        return seed[3]

    assert build(keys[0], 3, tmp_path / "cold").get("compiles", 0) >= 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        warm = build(keys[1], 3, tmp_path / "warm")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert "compiles" not in warm
    assert build(keys[2], 4, tmp_path / "new_k").get("compiles", 0) >= 1


def test_add_sums_into_the_innermost_span(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("outer", tag="a"):
            trace.add(bytes=1)
            with trace.span("inner"):
                trace.add(bytes=2)
                trace.add(bytes=3)
    (thread,) = _spans(tmp_path)
    got = {e[0]: e[3] for e in thread}
    assert got["repro.outer"] == {"tag": "a", "bytes": 1}
    assert got["repro.inner"] == {"bytes": 5}


@pytest.mark.parametrize("engine", ["materialized", "streamed", "pipelined"])
def test_draws_bit_identical_with_the_profiler_on_and_off(engine, tmp_path):
    k = jax.random.PRNGKey(11)
    off = _build(engine, k)
    with jax.profiler.trace(str(tmp_path)):
        on = _build(engine, k)
    assert np.array_equal(off[0], on[0])
    assert np.array_equal(off[1], on[1])
    assert off[2] == on[2]


def test_device_scopes_name_the_dis_rounds():
    from repro.core.dis import _key_chain, dis_plan_full
    from repro.core.streaming import _group_candidates

    text = jax.jit(lambda k, s: dis_plan_full(k, s, 4)).lower(
        jax.random.PRNGKey(1), jnp.ones((3, 50))).as_text(debug_info=True)
    for scope in ("dis_round1", "dis_round2", "dis_round3"):
        assert scope in text, scope
    i = jnp.array([0, 1])
    text = _group_candidates.lower(
        jnp.ones((2, 3, 16)), _key_chain(jax.random.PRNGKey(0), 7), i, i, i, i,
        30, cap=8, take=2, head=False).as_text(debug_info=True)
    assert "dis_redraw" in text
