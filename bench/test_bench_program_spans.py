"""The reduction of the program's ``repro.*`` spans and the readers built
on it, on synthetic planes and on a traced tiny run."""

from __future__ import annotations

import json
from types import SimpleNamespace as NS

import pytest

from bench import program_spans as ps

NEW = {"stage_s.build": "s", "idle_stage.build": "%", "dis_s.build": "s",
       "idle_compile.build": "%"}


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def planes(host_events=None, ops=None):
    """Window [0, 1000); build [100, 900) > dis [200, 700) > stage [300, 400)
    and wait [600, 700); a compile of 150 ns ending at 500; the device busy
    on [0, 100), [400, 450) and [800, 1000)."""
    if host_events is None:
        host_events = [
            ev("bench.window", 0, 1000), ev("bench.build", 90, 950),
            ev("repro.build", 100, 900, build=1, engine="pipelined"),
            ev("repro.dis", 200, 700), ev("repro.stage", 300, 400, bytes=64),
            ev("repro.compile", 500, 500, secs=150e-9),
            ev("repro.wait", 600, 700, of="rows"), ev("python_call", 0, 1000)]
    if ops is None:
        ops = [ev("fusion.1", 0, 100),
               ev("while.2", 400, 450),
               ev("fusion.3", 800, 1000)]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=host_events)])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    return [host, dev]


def test_self_time_subtracts_child_spans():
    s = ps.reduce_planes(planes())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.count == {"repro.build": 1, "repro.dis": 1, "repro.stage": 1,
                       "repro.wait": 1}
    assert s.total_s["repro.build"] == pytest.approx(800e-9)
    assert s.self_s["repro.build"] == pytest.approx(300e-9)
    assert s.self_s["repro.dis"] == pytest.approx(300e-9)
    assert s.self_s["repro.stage"] == pytest.approx(100e-9)


def test_idle_is_put_down_to_the_innermost_open_span():
    s = ps.reduce_planes(planes())
    assert s.idle_s == {"repro.build": pytest.approx(200e-9),
                        "repro.dis": pytest.approx(250e-9),
                        "repro.stage": pytest.approx(100e-9),
                        "repro.wait": pytest.approx(100e-9)}
    assert sum(s.idle_s.values()) == pytest.approx(650e-9)   # the device's idle


def test_idle_outside_every_span_is_unattributed():
    host = [ev("bench.window", 0, 1000), ev("repro.build", 100, 300)]
    s = ps.reduce_planes(planes(host, [ev("f", 300, 400)]))
    assert s.idle_s == {None: pytest.approx(700e-9),
                        "repro.build": pytest.approx(200e-9)}


def test_compile_interval_is_rebuilt_from_the_marker():
    s = ps.reduce_planes(planes())
    assert s.compiles == 1
    # [350, 500) against the idle [100, 400) and [450, 800)
    assert s.compile_idle_s == pytest.approx(100e-9)


def test_a_trace_without_program_spans_reduces_to_none():
    host = [ev("bench.window", 0, 1000), ev("bench.build", 100, 900)]
    assert ps.reduce_planes(planes(host)) is None


def test_spans_are_clipped_to_the_window():
    host = [ev("bench.window", 100, 600), ev("repro.build", 0, 1000),
            ev("repro.dis", 500, 800)]
    s = ps.reduce_planes(planes(host, []))
    assert s.total_s["repro.build"] == pytest.approx(500e-9)
    assert s.self_s["repro.build"] == pytest.approx(400e-9)
    assert s.total_s["repro.dis"] == pytest.approx(100e-9)


def test_workload_lookup_matches_configuration_and_traffic(tiny_root):
    cfg = json.loads((tiny_root / "bench" / "configs" / "tiny-ridge.json").read_text())
    pipe = json.loads((tiny_root / "bench" / "traffic" / "tiny-pipe.json").read_text())
    mat = json.loads((tiny_root / "bench" / "traffic" / "tiny-mat.json").read_text())
    assert ps.workload_of(cfg, pipe, tiny_root) == "ridge.pipe"
    assert ps.workload_of(cfg, mat, tiny_root) == "ridge.mat"
    assert ps.workload_of(dict(cfg, name="other"), pipe, tiny_root) is None
    assert ps.workload_of(cfg, dict(pipe, chunk_blocks=3), tiny_root) is None


def test_readers_return_none_without_a_trace(tiny_root):
    from bench import harness

    ctx = NS(trace=None, completed=3, config={}, traffic={})
    for name in NEW:
        assert harness.load_reader(name, tiny_root)(ctx) is None


@pytest.mark.parametrize("cell", ["ridge.pipe", "ridge.mat"])
def test_traced_tiny_run_reports_the_span_metrics(cell, tiny_root, run_cell):
    path = tiny_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for name, unit in NEW.items():
        bench["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                   "source": "device_trace", "layer": "test",
                                   "moves": "build_s"})
    path.write_text(json.dumps(bench))
    res, _ = run_cell(cell, trace=True)
    got = res["metrics"]
    assert {"dis_s.build", "idle_compile.build"} <= set(got)
    assert ("stage_s.build" in got) == (cell == "ridge.pipe")
    assert ("idle_stage.build" in got) == (cell == "ridge.pipe")
    assert got["dis_s.build"]["value"] > 0
    assert 0 <= got["idle_compile.build"]["value"] <= 100
    if cell == "ridge.pipe":
        assert got["stage_s.build"]["value"] > 0
        assert 0 < got["idle_stage.build"]["value"] <= 100
