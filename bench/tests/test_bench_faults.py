"""The comparison that decides ``correct`` fails on a broken timed path.

The harness runs end to end at a tiny size on the CPU, with a fault
planted where answers are produced (``bench.probe.Probe``): an answer
altered, half of each batch left out, an acknowledged ingest that leaves
the state unchanged, and the control (the reference computed in float32
in the program's place).  Each must read ``correct`` false.
"""

import pytest

from test_bench_cells import BIG, run

Y_OPEN = "ycsb_hashed_32m.c_open"
Y_BULK = "ycsb_hashed_32m.c_bulk"
S_LOAD = "sosd_lognormal_4m.load_read"
S_BULK = "sosd_lognormal_4m.bulk"


@pytest.mark.parametrize("workload,fault", [
    (Y_OPEN, "alter_answer"), (Y_OPEN, "half_batch"),
    (Y_BULK, "alter_answer"), (Y_BULK, "half_batch"),
    (S_BULK, "half_batch"),
    (S_LOAD, "alter_answer"), (S_LOAD, "unchanged_ingest"),
])
def test_fault_reads_incorrect(workload, fault):
    res = run(workload, fault)
    assert not res["correct"]
    assert sum(c["value"] for c in res["checks"].values()) > 0


def test_float32_control_reads_incorrect():
    assert run(Y_BULK, overrides=BIG)["correct"]
    res = run(Y_BULK, "control_f32", overrides=BIG)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
