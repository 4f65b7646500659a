"""Each cell's phases at a tiny size on the CPU, checked against the plain
reference: build, warm, one window of traffic, every answer compared."""

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
