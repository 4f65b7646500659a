"""The served path's host spans (``repro.*``): each step of a lookup or
an ingest opens a ``jax.profiler.TraceAnnotation``, nested as documented,
so a profiler trace names the step that took each millisecond.  Read
back here from a real trace of a small ``MicroBatchQueue`` ->
``EpochPipeline`` -> ``Index`` run on the CPU."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.core import Index
from repro.serving import EpochPipeline, MicroBatchQueue

SPANS = (
    "repro.queue.flush", "repro.queue.stage", "repro.queue.demux",
    "repro.pipeline.lookup", "repro.pipeline.ingest",
    "repro.pipeline.publish", "repro.index.lookup", "repro.index.sync",
    "repro.index.ingest", "repro.index.place", "repro.index.insert",
    "repro.index.bound_refresh", "repro.ops.delta_update",
    "repro.ops.freeze", "repro.engine.prep", "repro.engine.put",
    "repro.engine.dispatch", "repro.engine.fetch",
    "repro.engine.escape_patch", "repro.engine.host_views")


def _spans(log_dir):
    """``(name, start, end, line)`` of every ``repro.`` span on the host;
    ``line`` tells the thread lines apart."""
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for p, plane in enumerate(jax.profiler.ProfileData.from_file(path)
                              .planes):
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, (p, i)))
    return out


def _parent(spans, child, name):
    """The ``name`` span enclosing ``child`` on its thread line."""
    got = [s for s in spans if s[0] == name and s[3] == child[3]
           and s[1] <= child[1] and child[2] <= s[2]]
    assert len(got) == 1, (child, name, got)
    return got[0]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Lognormal keys with a run of 2000 consecutive integers, narrower
    than one row of the rank router, so the run's row holds far more
    slots than the bisect budget: lookups of the run escape and the
    host escape patch runs.  Served through the queue: a lookup, an
    ingest with a delta sync, lookups after it, and a refreeze, all
    traced.  Records each lookup call's rebuild of the escape patch's
    host copy."""
    rng = np.random.default_rng(0)
    keys = np.unique(np.floor(1e9 * rng.lognormal(0.0, 2.0, 24_000)))
    run = keys[keys.size // 2] + np.arange(1.0, 2_001.0)
    keys = np.union1d(keys, run)
    new = np.setdiff1d(np.unique(np.floor(
        1e9 * rng.lognormal(0.0, 2.0, 2_000))), keys)[:1_024]
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.15)
    idx.sync_device()
    q = MicroBatchQueue(EpochPipeline(idx, publish_every=1))
    probe = rng.choice(run, 600)
    rebuilds, reps = 0, []
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        for step in range(5):
            if step == 2:
                reps.append(q.result(q.submit_ingest(
                    new, np.arange(new.size, dtype=np.int64))))
            before = idx._engine._host_cache
            res = q.result(q.submit_lookup(probe))
            rebuilds += idx._engine._host_cache is not before
            assert res.found.all() and res.backend == "fused"
        idx.refreeze()
    q.close()
    return _spans(log_dir), rebuilds, reps


def test_every_documented_span_is_written(traced):
    spans, _, reps = traced
    assert reps[0].device == "delta"
    names = {s[0] for s in spans}
    assert set(SPANS) <= names, set(SPANS) - names


def test_a_lookup_nests_flush_pipeline_index_fetch(traced):
    spans, _, _ = traced
    fetches = [s for s in spans if s[0] == "repro.engine.fetch"]
    assert len(fetches) == 5
    for f in fetches:
        look = _parent(spans, f, "repro.index.lookup")
        pipe = _parent(spans, look, "repro.pipeline.lookup")
        _parent(spans, pipe, "repro.queue.flush")
        # fetch and the escape patch are siblings: they never overlap
        for e in spans:
            if e[0] == "repro.engine.escape_patch" and e[3] == f[3]:
                assert e[2] <= f[1] or f[2] <= e[1]


def test_an_ingest_writes_insert_and_sync_inside_it(traced):
    spans, _, _ = traced
    ingests = [s for s in spans if s[0] == "repro.index.ingest"]
    assert len(ingests) == 1
    for name in ("repro.index.insert", "repro.index.sync"):
        inner = [s for s in spans if s[0] == name]
        assert inner
        assert all(_parent(spans, s, "repro.index.ingest") == ingests[0]
                   for s in inner)
    _parent(spans, ingests[0], "repro.pipeline.ingest")


def test_host_views_span_counts_the_rebuilds(traced):
    """The first escaping lookup builds the host copy, the delta of the
    ingest invalidates it, the next escaping lookup rebuilds it: one
    span per rebuild and none for a cached read."""
    spans, rebuilds, _ = traced
    views = [s for s in spans if s[0] == "repro.engine.host_views"]
    assert rebuilds == 2
    assert len(views) == rebuilds
    for v in views:
        _parent(spans, v, "repro.engine.escape_patch")
