"""A configuration, a key generator, a traffic mix and a per-layer metric
added as files alone are found by the harness by name."""

import json
import shutil

import pytest

from bench import harness

GRID_KEYS = '''"""Evenly spaced integer keys with a random offset."""

import numpy as np


def sample(rng, size, *, step):
    return (rng.integers(0, 1 << 30, size) * int(step)).astype(np.float64)
'''

CALLS_METRIC = '''"""Lookup calls per second of window."""


def read(run):
    return len(run.calls_in_window(run.lookups)) / run.seconds
'''


def make_root(tmp_path):
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    for d in ("metrics", "keys"):
        shutil.copytree(harness.ROOT / "bench" / d, root / "bench" / d)
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "keys" / "grid.py").write_text(GRID_KEYS)
    (root / "bench" / "metrics" / "calls_per_s.tiny.py").write_text(
        CALLS_METRIC)
    (root / "bench" / "configs" / "grid_small.json").write_text(json.dumps({
        "name": "grid_small", "records": 5000, "key_limit": 2 ** 48,
        "keys": {"generator": "grid", "params": {"step": 7}},
        "build": {"method": "pgm", "gap_rho": 0.15, "sample_rate": 0.05},
        "queue": {"max_wait_ms": 1.0}}))
    (root / "bench" / "traffic" / "tiny_open.json").write_text(json.dumps({
        "open": {"rate_per_s": 200, "keys": "scrambled_zipfian",
                 "theta": 0.9},
        "warm_buckets": [512], "trace_seconds": 0.3}))
    base = harness.load_spec()
    spec = {
        "configs": [{"name": "grid_small", "source": "test",
                     "file": "bench/configs/grid_small.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "grid_small.tiny_open", "config": "grid_small",
                       "traffic": "tiny_open", "chips": 1, "why": "test"}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in base["end_to_end"]
                       if m["name"] in ("lookup_p75_ms", "setup_s",
                                        "build_s")],
        "per_layer": [{"name": "calls_per_s.tiny", "unit": "calls/s",
                       "better": "higher", "source": "host_clock",
                       "layer": "serving queue (MicroBatchQueue)",
                       "moves": "lookup_p75_ms"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_files_alone_add_a_cell_and_a_metric(tmp_path):
    root = make_root(tmp_path)
    spec = harness.load_spec(root)
    cell, cfg, traffic = harness.find_cell(spec, "grid_small.tiny_open", root)
    assert cfg["keys"]["generator"] == "grid" and "open" in traffic
    # without a workloads key, the metric follows the end-to-end metric
    # it moves
    assert [m["name"] for m in harness.cell_metrics(
        spec, "grid_small.tiny_open", "per_layer")] == ["calls_per_s.tiny"]
    e2e = harness.run_cell(root, spec, "grid_small.tiny_open", 3, 1.0, 0,
                           log=lambda rec: None)
    assert e2e["correct"]
    assert set(e2e["metrics"]) == {"lookup_p75_ms", "setup_s", "build_s"}
    layer = harness.run_cell(root, spec, "grid_small.tiny_open", 4, 1.5, 1,
                             log=lambda rec: None)
    assert layer["correct"]
    assert set(layer["metrics"]) == {"calls_per_s.tiny"}
    assert layer["metrics"]["calls_per_s.tiny"]["value"] > 0
    assert set(layer["breakdown"]) == {"device_ops", "idle_gaps"}
    assert (root / "bench" / "out" / "trace-grid_small.tiny_open-4"
            / "trace_trimmed.json").is_file()


def test_files_alone_add_a_sharded_cell_on_four_chips(tmp_path):
    """A configuration whose build names ``shards`` and a four-chip
    workload: a range-sharded index served by the shard fan-out."""
    root = make_root(tmp_path)
    cfg = json.loads((root / "bench" / "configs" / "grid_small.json")
                     .read_text())
    cfg["build"]["shards"] = 4
    (root / "bench" / "configs" / "grid_sharded.json").write_text(
        json.dumps(dict(cfg, name="grid_sharded")))
    (root / "bench" / "traffic" / "tiny_bulk.json").write_text(json.dumps({
        "bulk": {"batch_keys": 1024, "present_share": 0.5,
                 "pool_batches": 2},
        "warm_buckets": [1024], "trace_seconds": 0.3}))
    spec = harness.load_spec(root)
    spec["configs"].append(dict(spec["configs"][0], name="grid_sharded",
                                file="bench/configs/grid_sharded.json"))
    spec["workloads"] = [{"name": "grid_sharded.tiny_bulk",
                          "config": "grid_sharded", "traffic": "tiny_bulk",
                          "chips": 4, "why": "test"}]
    spec["end_to_end"] = [
        {k: v for k, v in m.items() if k != "workloads"}
        for m in harness.load_spec()["end_to_end"]
        if m["name"] in ("lookup_keys_per_s", "setup_s", "build_s")]
    spec["per_layer"][0]["moves"] = "lookup_keys_per_s"
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    spec = harness.load_spec(root)
    recs = []
    e2e = harness.run_cell(root, spec, "grid_sharded.tiny_bulk", 5, 1.0, 0,
                           log=recs.append)
    assert e2e["correct"]
    assert set(e2e["metrics"]) == {"lookup_keys_per_s", "setup_s",
                                   "build_s"}
    phase = {r["phase"]: r for r in recs if "phase" in r}
    assert phase["build"]["fused_impl"] == "fanout"
    assert phase["window"]["fanout_lookups"] == (
        phase["window"]["lookup_calls"]) > 0
    layer = harness.run_cell(root, spec, "grid_sharded.tiny_bulk", 6, 1.5,
                             1, log=lambda rec: None)
    assert layer["correct"]
    assert layer["metrics"]["calls_per_s.tiny"]["value"] > 0
    assert set(layer["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_split_metric_name_falls_back_to_its_base_reader(tmp_path):
    d = tmp_path / "bench" / "metrics"
    d.mkdir(parents=True)
    (d / "busy.py").write_text("def read(run):\n    return 1.0\n")
    (d / "busy.load.py").write_text("def read(run):\n    return 2.0\n")
    assert harness.metric_file(tmp_path, "busy.open") == d / "busy.py"
    assert harness.metric_file(tmp_path, "busy.load") == d / "busy.load.py"
    assert harness.metric_file(tmp_path, "busy") == d / "busy.py"
    with pytest.raises(harness.BenchError):
        harness.metric_file(tmp_path, "idle.open")
