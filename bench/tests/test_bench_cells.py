"""Each cell's phases at a tiny size on the CPU, checked against the plain
reference: build, warm, one window of traffic, every answer compared."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
# the bulk mix over both configurations, whether or not the benchmark
# lists those cells: the tests drive every mode of the generator
BULK = {"ycsb_hashed_32m.c_bulk": "ycsb_hashed_32m",
        "sosd_lognormal_4m.bulk": "sosd_lognormal_4m"}
ALL = dict(SPEC, workloads=SPEC["workloads"] + [
    {"name": n, "config": c, "traffic": "bulk", "chips": 1, "why": "test"}
    for n, c in BULK.items() if n not in CELLS])
SEED = (1 << 33) + 12345   # wider than 32 bits, as seeds may be

# tiny sizes: the shapes and the code path of each cell, scaled down
SMALL = {"config": {"records": 20000, "insert_pool": 8192},
         "traffic": {"open": {"rate_per_s": 300}, "warm_buckets": [512, 1024],
                     "bulk": {"batch_keys": 1024, "pool_batches": 2},
                     "load": {"batch_keys": 1024, "warm_batches": 1}}}

# a range-partitioned index over four chips: the bulk mix, whose
# 1024-key batches are large enough for the shard fan-out
FANOUT = "ycsb_hashed_32m.fanout"
ALL["workloads"].append({"name": FANOUT, "config": "ycsb_hashed_32m",
                         "traffic": "bulk", "chips": 4, "why": "test"})


def shards(overrides, k=4):
    """``overrides`` that also build ``k`` range shards."""
    return dict(overrides, config=dict(overrides["config"],
                                       build={"shards": k}))


SHARDED = shards(SMALL)
# float32 keys alias once enough keys share a float32 value: at 2^16
# uniform keys about 128 pairs do, so 4 batches of 2048 stored keys hit
# some
BIG = harness.merge(SMALL, {"config": {"records": 1 << 16},
                            "traffic": {"warm_buckets": [2048],
                                        "bulk": {"batch_keys": 2048,
                                                 "pool_batches": 4}}})


def run(workload, fault=None, overrides=SMALL, seconds=1.5):
    return harness.run_cell(harness.ROOT, ALL, workload, SEED, seconds, 0,
                            fault=fault, overrides=overrides,
                            log=lambda rec: None)


@pytest.mark.parametrize("workload", sorted(set(CELLS) | set(BULK)))
def test_cell_is_correct_at_a_tiny_size(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in harness.cell_metrics(ALL, workload,
                                                     "end_to_end")}
    assert set(res["metrics"]) == names
    assert "setup_s" in names and len(names) >= 3
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["limit"] == 0 for c in res["checks"].values())


def test_attribution_maps_each_request_to_the_call_that_served_it():
    class R:
        due = np.array([0.0, 0.1, 0.2, 0.3, 0.4])
        submit = due + 0.01
        n_submitted = 5
    # three calls; the queue had coalesced 7 lookups before the window
    calls = [(1.05, 1.2, 7, 512, 0), (1.25, 1.3, 9, 512, 0),
             (1.45, 1.5, 10, 512, 0)]
    w = {"reader": R, "lookups": calls, "base": 7, "t0": 1.0}
    a = harness.attribute(w)
    np.testing.assert_array_equal(a["end"], [1.2, 1.2, 1.3, 1.5, 1.5])
    np.testing.assert_array_equal(a["start"], [1.05, 1.05, 1.25, 1.45, 1.45])
    np.testing.assert_array_equal(a["call_keys"], [2, 1, 2])
    np.testing.assert_allclose(a["due"], R.due + 1.0)
    assert a["served"].all()


def test_main_refuses_a_cpu(capsys):
    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_main_refuses_an_unknown_workload(capsys):
    rc = harness.main(["--workload", "no_such.cell", "--seed", "1",
                       "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    has no system under test: the command exits non-zero, no result."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def sharded_run(fault=None, overrides=SHARDED):
    """A fan-out cell's result and its build and window records."""
    recs = []
    res = harness.run_cell(harness.ROOT, ALL, FANOUT, SEED, 1.5, 0,
                           fault=fault, overrides=overrides,
                           log=recs.append)
    phase = {r["phase"]: r for r in recs if "phase" in r}
    return res, phase["build"], phase["window"]


def check_fanout_run(res, build, win, devices):
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == devices
    assert build["fused_impl"] == "fanout" and build["fanout_shards"] == 4
    assert build["fanout_devices"] == devices
    assert len(build["n_slots_per_shard"]) == 4
    assert build["n_slots"] == sum(build["n_slots_per_shard"])
    # every call in the window went through the fan-out
    assert win["lookup_calls"] > 0
    assert win["fanout_lookups"] == win["lookup_calls"]
    assert win["compiles_in_window"] == 0


def test_sharded_cell_serves_every_call_through_the_fanout():
    import jax

    res, build, win = sharded_run()
    check_fanout_run(res, build, win, min(4, len(jax.devices())))


FOUR_DEVICES = """
import json
import numpy as np
from bench import harness
from bench.tests import test_bench_cells as t
from repro.core import Index
res, build, win = t.sharded_run()
# a fan-out over four devices does not fit a two-chip cell
index = Index.build(np.arange(1.0, 4001.0), shards=4, gap_rho=0.15)
try:
    harness.sync_fanout(index, 2)
    refused = False
except harness.BenchError:
    refused = True
print(json.dumps([res, build, win, refused], default=harness._jsonable))
"""


def test_sharded_cell_exchanges_over_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT / "src"),
                                           str(harness.ROOT)]))
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res, build, win, refused = json.loads(p.stdout.strip().splitlines()[-1])
    check_fanout_run(res, build, win, 4)
    assert refused


def test_float32_control_reads_incorrect_on_the_sharded_path():
    res, _, _ = sharded_run(overrides=shards(BIG))
    assert res["correct"]
    res, _, _ = sharded_run("control_f32", overrides=shards(BIG))
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_a_warm_up_that_misses_the_fanout_fails_set_up():
    over = harness.merge(SHARDED, {"traffic": {"warm_buckets": [256]}})
    with pytest.raises(harness.BenchError, match="reached the shard fan-out"):
        sharded_run(overrides=over)


def test_sync_fanout_builds_the_fanout_once_and_never_falls_back(
        monkeypatch):
    import repro.kernels.shard_fanout as sf
    from repro.core import Index

    keys = np.arange(1, 4001, dtype=np.float64) * 3
    index = Index.build(keys, shards=4, method="pgm", gap_rho=0.15)
    fan = harness.sync_fanout(index, 4)
    assert isinstance(fan, sf.ShardFanout) and fan.S == 4
    assert harness.sync_fanout(index, 4) is fan   # no shard changed

    def refuse(cls, *a, **k):
        raise sf.FanoutUnavailable("a shard exports no PLM")

    monkeypatch.setattr(sf.ShardFanout, "build", classmethod(refuse))
    other = Index.build(keys, shards=4, method="pgm", gap_rho=0.15)
    with pytest.raises(harness.BenchError, match="cannot serve"):
        harness.sync_fanout(other, 4)
