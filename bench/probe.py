"""What the benchmark puts around the served path: a thin proxy between
``MicroBatchQueue`` and ``EpochPipeline`` that records every call's
start and end on the host clock, the compile counter, and the faults
and the control that the correctness tests plant there.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

# Faults planted where answers are produced.  None of the benchmark's
# own runs plants one; ``bench/tests/test_bench_faults.py`` and the
# ``--fault`` option do.
FAULTS = ("alter_answer", "half_batch", "unchanged_ingest", "control_f32")


class Probe:
    """Stands in for the pipeline as the queue's ``index``.  Each lookup
    call records ``(start, end, lookups_before, rows, escapes)``:
    ``lookups_before`` is the queue's count of coalesced lookups when the
    call began (read under the queue's lock, which ``flush`` holds), so
    request ``i`` of a single submitting thread is served by the call
    whose count range holds ``i``.  Each ingest call records ``(start,
    end, keys, report)``."""

    def __init__(self, pipe, *, fault: str | None = None, control=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        self.pipe = pipe
        self.queue = None
        self.fault = fault
        self.control = control   # Reference in float32 (control_f32)
        self.lookups: list = []
        self.ingests: list = []
        self.span = _no_span

    @property
    def epoch(self) -> int:
        return self.pipe.epoch

    @property
    def stats(self) -> dict:
        return self.pipe.stats

    def lookup(self, queries, *, backend=None):
        before = self.queue.stats["coalesced_lookups"]
        t0 = time.perf_counter()
        with self.span("bench.pipeline.lookup"):
            if self.fault == "control_f32":
                res = self._control_lookup(queries)
            else:
                res = self.pipe.lookup(queries, backend=backend)
        t1 = time.perf_counter()
        self.lookups.append((t0, t1, before, len(queries),
                             int(res.fallbacks)))
        if self.fault == "alter_answer":
            pay = np.array(res.payloads)
            pay[0] += 1
            res = dataclasses.replace(res, payloads=pay)
        elif self.fault == "half_batch":
            pay, found = np.array(res.payloads), np.array(res.found)
            pay[::2], found[::2] = -1, False   # every other row left out
            res = dataclasses.replace(res, payloads=pay, found=found)
        return res

    def _control_lookup(self, queries):
        from repro.core.results import LookupResult

        epoch = self.pipe.epoch
        pay, found = self.control.lookup(queries, epoch)
        return LookupResult(payloads=pay, slots=np.zeros(pay.size, np.int64),
                            found=found, backend="control-f32", epoch=epoch)

    def ingest(self, keys, payloads):
        t0 = time.perf_counter()
        with self.span("bench.pipeline.ingest"):
            if self.fault == "unchanged_ingest":
                from repro.core.results import IngestReport

                n = int(np.asarray(keys).shape[0])
                rep = IngestReport(n=n, slot=n, chain=0, contested=0,
                                   epoch=self.pipe.epoch)
            else:
                rep = self.pipe.ingest(keys, payloads)
        if self.control is not None:
            self.control.mark(keys, rep.epoch)
        self.ingests.append((t0, time.perf_counter(), int(len(keys)), rep))
        return rep


@contextlib.contextmanager
def _no_span(_name):
    yield


def trace_span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def annotate_flush(queue) -> None:
    """Wrap the queue's ``flush`` (called by its deadline timer and by
    ``result``) in a host span, for traced runs."""
    inner = queue.flush

    def flush():
        with trace_span("bench.queue.flush"):
            inner()

    queue.flush = flush


class CompileClock:
    """Backend compile seconds and count in this process, summed from
    JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self) -> tuple:
        return self.seconds, self.count

    def since(self, mark) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.count - mark[1]}


def peak_bytes(devices) -> list:
    """Peak bytes in use on each of ``devices``, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks
