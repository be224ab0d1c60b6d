"""The trace reduction, the peak table and the work functions."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from bench import peaks, trace, work


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000), ev("bench.plan", 40, 100),
        ev("bench.build", 100, 900), ev("other", 0, 1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_x", 0, 1000)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 100, 300),
            ev("custom-call.2", 250, 400, hlo_op="x", custom_call_target="tpu_custom_call"),
            ev("fusion.1", 600, 700),
            ev("late", 1100, 1200)])])
    return [host, dev]


def test_busy_kernel_and_idle_gaps():
    s = trace.reduce_planes(planes())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.kernel_events == 1
    assert s.kernel_union_s == pytest.approx(150e-9)
    assert s.kernel_sum_s == pytest.approx(150e-9)
    assert s.top_ops == [("fusion.1", pytest.approx(300e-9)),
                         ("custom-call.2", pytest.approx(150e-9))]
    assert s.idle_gaps == [("bench.build", pytest.approx(300e-9)),
                           ("bench.build", pytest.approx(200e-9)),
                           ("bench.plan", pytest.approx(100e-9))]
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"} and len(b["device_ops"]) == 2


def test_two_devices_are_averaged():
    ps = planes()
    ps.append(NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        ev("fusion.9", 0, 1000)])]))
    s = trace.reduce_planes(ps)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(700e-9)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_planes(planes()[1:])


def test_a_trace_without_device_ops_is_refused():
    """A device plane whose op line is named otherwise fails the run
    instead of reading an idle chip."""
    host, dev = planes()
    dev.lines = [line for line in dev.lines if line.name != "XLA Ops"]
    with pytest.raises(ValueError, match="XLA Modules"):
        trace.reduce_planes([host, dev])


def test_reduces_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.build"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = trace.reduce(trace.find_xplane(tmp_path))
    assert s.window_s > 0
    assert "plane '/host:CPU'" in trace.describe(trace.find_xplane(tmp_path))


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def test_work_at_yearmsd_widths_by_hand():
    n = 515_345
    _, b = work.leverage(n, [30, 30, 31])
    assert b == 4 * (n * 91 + 900 + 900 + 961 + 3 * n) == 193_780_764
    with pytest.raises(ValueError):
        work.score_pass({"task": "vkmc", "n": n, "d": 90, "T": 3})
    ridge = {"task": "vrlr", "n": n, "d": 90, "T": 3}
    f, b = work.score_pass(ridge)
    assert b == 193_780_764
    assert f == n * sum(2 * w * w + 2 * w for w in (30, 30, 31))
    least, bound = work.least_seconds(f, b, peaks.peaks_for("TPU v5 lite"))
    assert bound == "hbm" and least == pytest.approx(b / 819e9)
