"""The harness is driven by data: cells, mixes, configurations and
metrics are found by name, and the last line holds the result."""

from __future__ import annotations

import json

import pytest

from bench import harness, run


def test_new_config_traffic_and_metric_files_need_no_edit(run_cell):
    res, line = run_cell("ridge.mat")
    assert list(res) == list(harness.RESULT_KEYS) + ["checks"]
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "build_s", "builds_done"}
    assert res["metrics"]["builds_done"]["value"] == res["attempted"]
    assert res["metrics"]["build_s"]["unit"] == "s"
    assert all(set(v) == {"value", "limit"} for v in res["checks"].values())
    assert line.count("\n") == 0


def test_new_driver_file_needs_no_edit(run_cell):
    """A mix naming a driver no file of the harness knows, read by a metric
    that sees the driver's own records."""
    res, line = run_cell("ridge.count")
    assert list(res) == list(harness.RESULT_KEYS) + ["checks"]
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "build_s", "largest_key"}
    assert res["metrics"]["largest_key"]["value"] > 0
    assert res["checks"] == {"requests_lost": {"value": 0.0, "limit": 0.0}}


def test_metrics_are_those_the_cell_lists(run_cell):
    res, _ = run_cell("ridge.pipe")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "build_s"}


def test_traced_run_reports_per_layer_metrics_and_breakdown(run_cell):
    res, _ = run_cell("ridge.pipe", trace=True)
    assert list(res) == list(harness.RESULT_KEYS) + ["breakdown", "checks"]
    assert res["correct"] is True
    assert "plan_s.build" in res["metrics"] and "build_s" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_builds_driver_refuses_a_task_it_cannot_check(tiny_root):
    import jax

    from bench import harness as h

    Driver = h.load_driver({"driver": "builds"}, tiny_root)
    cfg = {"task": "vkmc", "n": 3000, "d": 9, "T": 3, "m": 32}
    d = Driver(jax, cfg, {"engine": "materialized", "resident": "device"}, 1,
               h.Spans(jax, False))
    with pytest.raises(ValueError, match="vrlr"):
        d.setup()


def test_unknown_workload_is_an_error(tiny_root):
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", tiny_root)


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    rc = run.main(["--workload", "ridge.materialized", "--seed", str(2 ** 32 + 3),
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "not a TPU" in out.err


def test_every_cell_of_the_benchmark_resolves():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_reader(m["name"]))


def test_result_line_keeps_checks_last():
    line = harness.result_line(True, 3, 0, {}, {"platform": "tpu"},
                               [harness.Check("a", 1.0, 2.0)], {"device_ops": []})
    assert list(json.loads(line)) == ["correct", "attempted", "failed", "metrics",
                                      "device", "breakdown", "checks"]
