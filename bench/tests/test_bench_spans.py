"""The reduction of the program's own spans (``repro.*``) in a profiler
trace: span listing, self time on one thread line, and the per-layer
readers built on them."""

import json
import pathlib

import pytest

from bench import harness, spans, trace

DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000.0
# this PR's readers: none finds anything in a trace of a program
# without the spans
READERS = ("lookup_host_ms.open", "lookup_transfer_ms.open",
           "escape_patch_ms.bulk", "flush_self_ms.open",
           "ingest_insert_ms.load", "ingest_sync_ms.load")


def synthetic():
    """A lookup on one thread line, an ingest on another, and a fetch on
    the second line that overlaps the first line's lookup in time."""
    def ev(name, a, b):
        return [name, a * MS, (b - a) * MS]
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ev("bench.window", 0, 100),
                ev("bench.queue.flush", 10, 40),
                ev("repro.queue.flush", 11, 39),
                ev("repro.pipeline.lookup", 15, 35),
                ev("repro.index.lookup", 16, 34),
                ev("repro.engine.put", 17, 18),
                ev("repro.engine.fetch", 25, 30),
                ev("repro.engine.escape_patch", 30, 33),
                ev("repro.engine.host_views", 30.5, 32)]},
            {"name": "python", "events": [
                ev("repro.engine.fetch", 20, 24),
                ev("repro.index.ingest", 50, 90),
                ev("repro.index.insert", 52, 60),
                ev("repro.index.sync", 60, 85),
                ev("repro.ops.delta_update", 61, 80),
                ev("repro.index.sync", 120, 130)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ev("gather", 20, 22), ev("fusion", 40, 45)]}]},
    ]}


def run_of(profile):
    return harness.Run(profile=profile,
                       trace_window=trace.traced_window(profile))


def read(name, run):
    return harness.load_module(
        harness.metric_file(harness.ROOT, name)).read(run)


def test_program_spans_carry_their_thread_line():
    p = synthetic()
    every = spans.program_spans(p)
    assert len(every) == 13
    assert all(s.name.startswith(spans.PROGRAM_PREFIX) for s in every)
    fetch = [s for s in every if s.name == "repro.engine.fetch"]
    assert fetch[0].line != fetch[1].line
    # the window keeps the spans that start in it
    late = spans.program_spans(p, (0.0, 100 * MS))
    assert len(late) == 12 and every[-1] not in late


def test_children_and_self_time_stay_on_one_thread_line():
    every = spans.program_spans(synthetic())
    tree = spans.Tree(every)
    look = next(s for s in every if s.name == "repro.index.lookup")
    kids = tree.children(look)
    # the other line's fetch overlaps in time but is not a child
    assert {s.name for s in kids} == {
        "repro.engine.put", "repro.engine.fetch",
        "repro.engine.escape_patch", "repro.engine.host_views"}
    away = tree.children(look, ("repro.engine.put", "repro.engine.fetch",
                                "repro.engine.escape_patch"))
    assert spans.covered_ns(away) == pytest.approx(9 * MS)
    flush = next(s for s in every if s.name == "repro.queue.flush")
    assert [s.name for s in tree.children(flush, prefix="repro.pipeline.")
            ] == ["repro.pipeline.lookup"]


@pytest.mark.parametrize("name,call,part", [
    ("ingest_insert_ms.load", "repro.index.ingest", "repro.index.insert"),
    ("escape_patch_ms.load", "repro.index.lookup",
     "repro.engine.escape_patch")])
def test_parts_count_with_the_call_they_run_in(name, call, part):
    """A part whose call started before the window is left out, though
    it starts in the window; one whose call started in the window counts,
    though it runs past the window's end."""
    def ev(n, a, b):
        return [n, a * MS, (b - a) * MS]
    p = {"planes": [{"name": "/host:CPU", "lines": [{"name": "python",
         "events": [ev("bench.window", 100, 200),
                    ev(call, 90, 120), ev(part, 102, 115),
                    ev(call, 130, 160), ev(part, 132, 150),
                    ev(call, 190, 230), ev(part, 201, 220)]}]}]}
    assert read(name, run_of(p)) == pytest.approx(18.5)


@pytest.mark.parametrize("name,want", [
    ("lookup_host_ms.open", 9.0), ("lookup_host_ms.bulk", 9.0),
    ("lookup_transfer_ms.open", 6.0), ("escape_patch_ms.load", 3.0),
    ("flush_self_ms.open", 8.0), ("ingest_insert_ms.load", 8.0),
    ("ingest_sync_ms.load", 25.0)])
def test_readers_on_the_synthetic_trace(name, want):
    assert read(name, run_of(synthetic())) == pytest.approx(want)


def test_readers_find_nothing_without_program_spans():
    """A trace of the program before it wrote spans (recorded on the
    chip), and an untraced run: every reader returns None, none raises."""
    p = json.loads((DATA / "trace_ycsb_hashed_32m.c_open.json").read_text())
    for name in READERS:
        assert read(name, run_of(p)) is None
        assert read(name, harness.Run(profile=None, trace_window=None)
                    ) is None


def test_each_metric_entry_has_a_reader_and_its_cells():
    spec = harness.load_spec()
    cells = {w["name"] for w in spec["workloads"]}
    ours = [m for m in spec["per_layer"] if m["source"] == "program_span"
            and m["name"] != "learn_s"]
    assert len(ours) == 9
    for m in ours:
        assert harness.metric_file(harness.ROOT, m["name"]).is_file()
        assert m["workloads"] and set(m["workloads"]) <= cells


@pytest.mark.parametrize("cell", ["ycsb_hashed_32m.c_open",
                                  "sosd_lognormal_4m.load_read"])
def test_recorded_chip_traces_read_every_span_metric_of_their_cell(cell):
    """Excerpts of ``--trace 1`` runs on one TPU v5e: each metric the
    cell lists that reads the program's spans prints a number, and the
    program's lookup spans account for the benchmark's lookup calls."""
    p = json.loads((DATA / f"trace_spans_{cell}.json").read_text())
    run = run_of(p)
    names = [m["name"] for m in harness.load_spec()["per_layer"]
             if m["source"] == "program_span"
             and cell in m.get("workloads", [])]
    assert len(names) == 3
    for name in names:
        assert read(name, run) > 0.0
    # on the same clock, the program's lookup spans cover the calls the
    # benchmark times from outside
    lo, hi = run.trace_window
    outer = sum(e - s for n, s, e in trace.host_spans(p)
                if n == "bench.pipeline.lookup" and lo <= s < hi)
    inner = sum(s.end - s.start for s in spans.named(
        spans.program_spans(p), run.trace_window, "repro.index.lookup"))
    assert outer > 0 and inner >= 0.9 * outer
