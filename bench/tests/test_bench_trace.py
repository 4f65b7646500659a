"""The reduction from a profiler trace to device busy time, module time
and named idle gaps, and the roofline's byte count and peak table."""

import json
import pathlib

import pytest

from bench import roofline, trace

DATA = pathlib.Path(__file__).parent / "data"


def synthetic():
    ms = 1_000_000.0
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench.window", 0.0, 100 * ms],
                ["bench.queue.flush", 10 * ms, 30 * ms],
                ["bench.pipeline.lookup", 15 * ms, 20 * ms],
                ["not.ours", 50 * ms, 10 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["gather", 20 * ms, 5 * ms],
                ["fusion", 22 * ms, 8 * ms],     # overlaps: union 20-30
                ["gather", 60 * ms, 10 * ms],
                ["late", 95 * ms, 10 * ms]]},    # clipped at 100
            {"name": "XLA Modules", "events": [
                ["jit__fused_pipeline(7)", 20 * ms, 10 * ms],
                ["jit__other(1)", 60 * ms, 10 * ms],
                ["jit__fused_pipeline(7)", 95 * ms, 10 * ms]]}]},
    ]}


def test_busy_is_the_union_of_op_intervals_in_the_window():
    p = synthetic()
    w = trace.traced_window(p)
    assert w == (0.0, 100e6)
    assert trace.busy_seconds(p, w) == pytest.approx(0.025)


def test_module_time_counts_whole_executions_in_the_window():
    p = synthetic()
    secs, n = trace.module_time(p, "jit__fused_pipeline", (0.0, 100e6))
    assert (secs, n) == (pytest.approx(0.010), 1)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    gaps = dict(trace.idle_gaps(synthetic(), (0.0, 100e6)))
    # 0-20: flush opens at 10, lookup at 15; midpoint 10 -> flush
    assert gaps["bench.queue.flush"] == pytest.approx(0.020)
    # 30-60 (midpoint 45) and 70-95 (82.5): no benchmark span
    assert gaps["no benchmark span (generator, queue wait)"] == (
        pytest.approx(0.055))
    assert sum(gaps.values()) == pytest.approx(0.075)


def test_top_ops_sums_by_name():
    ops = trace.top_ops(synthetic(), (0.0, 100e6))
    assert ops[0] == ["gather", pytest.approx(0.015)]


def test_trim_keeps_the_window_and_our_spans():
    t = trace.trim(synthetic(), (0.0, 50e6))
    host = [e[0] for ln in t["planes"][0]["lines"] for e in ln["events"]]
    assert "not.ours" not in host and "bench.window" in host
    assert trace.busy_seconds(t, (0.0, 50e6)) == pytest.approx(0.010)


def two_chips():
    """``synthetic()`` with a second chip that runs other ops, and the
    runtime's plane that holds no chip's ops."""
    ms = 1_000_000.0
    p = synthetic()
    p["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["scatter", 40 * ms, 30 * ms]]},
        {"name": "XLA Modules", "events": [
            ["jit__fused_pipeline(7)", 40 * ms, 30 * ms]]}]})
    p["planes"].append({"name": "/device:CUSTOM:Megascale Trace",
                        "lines": []})
    return p


def test_a_run_reads_only_the_planes_of_its_chips():
    w = (0.0, 100e6)
    both = two_chips()
    assert trace.busy_seconds(both, w) == pytest.approx((0.025 + 0.030) / 2)
    assert [p["name"] for p in trace.device_planes(both)] == [
        "/device:TPU:0", "/device:TPU:1"]
    one = trace.keep_devices(both, [0])
    assert [p["name"] for p in trace.device_planes(one)] == ["/device:TPU:0"]
    assert [p["name"] for p in one["planes"]] == [
        "/host:CPU", "/device:TPU:0", "/device:CUSTOM:Megascale Trace"]
    assert trace.busy_seconds(one, w) == pytest.approx(0.025)
    assert trace.module_time(one, "jit__fused_pipeline", w) == (
        pytest.approx(0.010), 1)
    assert "scatter" not in dict(trace.top_ops(one, w))
    assert sum(v for _, v in trace.idle_gaps(one, w)) == pytest.approx(0.075)
    # the second chip alone, and both
    assert trace.busy_seconds(trace.keep_devices(both, [1]), w) == (
        pytest.approx(0.030))
    assert trace.keep_devices(both, [0, 1]) == both


def test_recorded_chip_traces_reduce_within_bounds():
    paths = sorted(DATA.glob("trace_*.json"))
    assert paths, "no recorded trace under bench/tests/data"
    for path in paths:
        assert path.stat().st_size < 1 << 20
        p = json.loads(path.read_text())
        # one chip recorded: the first chip's planes are the whole trace
        assert trace.keep_devices(p, [0]) == p
        w = trace.traced_window(p)
        busy = trace.busy_seconds(p, w)
        assert 0.0 < busy <= (w[1] - w[0]) / 1e9
        idle = sum(v for _, v in trace.idle_gaps(p, w, k=1000))
        assert busy + idle == pytest.approx((w[1] - w[0]) / 1e9, rel=1e-6)
        assert trace.top_ops(p, w)


def test_lookup_bytes_depend_on_batch_and_error_bound_only():
    a = roofline.lookup_bytes(65536, 64.0)
    assert a == 65536 * (8 + 8 + 8 * 8 + 8 + 9)   # ceil(log2(129)) = 8
    assert roofline.lookup_bytes(2 * 65536, 64.0) == 2 * a
    assert roofline.lookup_bytes(65536, 1000.0) > a
    assert roofline.probes(0.0) == 1


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    # the share at the peak itself is 100%
    assert roofline.hbm_roofline_pct(819_000, 1e-6, "TPU v5 lite") == (
        pytest.approx(100.0))
