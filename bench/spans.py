"""The program's own host spans (``repro.*``) in a profiler trace, and
the reductions the per-layer metrics make of them.

The served path opens a ``jax.profiler.TraceAnnotation`` at each step of
a call (``repro.queue.flush`` > ``repro.pipeline.lookup`` >
``repro.index.lookup`` > ``repro.engine.fetch`` ...), so its spans lie on
the same clock as the device's ops and the benchmark's ``bench.*``
spans.  A span's children are the spans nested inside it on the same
thread line: a span on another thread never counts toward a parent's
time.  Works on the dict ``bench.trace.load_profile`` returns.
"""

from __future__ import annotations

import bisect
from typing import NamedTuple

from bench import trace

PROGRAM_PREFIX = "repro."


class Span(NamedTuple):
    name: str
    start: float    # ns, profiler clock
    end: float
    line: tuple     # (plane index, line index): one host thread


def program_spans(profile: dict, window: tuple | None = None) -> list:
    """Every program span on the host, sorted by start; with ``window``,
    those that start in it."""
    out = []
    for p, plane in enumerate(profile["planes"]):
        if plane["name"].startswith("/device:"):
            continue
        for i, line in enumerate(plane["lines"]):
            for name, start, dur in line["events"]:
                if name.startswith(PROGRAM_PREFIX):
                    out.append(Span(name, start, start + dur, (p, i)))
    if window is not None:
        lo, hi = window
        out = [s for s in out if lo <= s.start < hi]
    return sorted(out, key=lambda s: (s.start, -s.end))


class Tree:
    """Program spans indexed by thread line, for child lookups."""

    def __init__(self, spans: list):
        self.by_line: dict = {}
        for s in spans:
            self.by_line.setdefault(s.line, []).append(s)
        self.starts = {ln: [s.start for s in ss]
                       for ln, ss in self.by_line.items()}

    def children(self, parent: Span, names=None, prefix=None) -> list:
        """Spans nested in ``parent`` on its thread line (any depth),
        with a name in ``names`` or starting with ``prefix``."""
        ss = self.by_line.get(parent.line, [])
        st = self.starts.get(parent.line, [])
        i0 = bisect.bisect_left(st, parent.start)
        i1 = bisect.bisect_right(st, parent.end)
        return [s for s in ss[i0:i1]
                if s is not parent and s.end <= parent.end
                and (names is None or s.name in names)
                and (prefix is None or s.name.startswith(prefix))]


def covered_ns(spans) -> float:
    """Length of the union of the spans' intervals."""
    return sum(e - s for s, e in trace.union(
        [(s.start, s.end) for s in spans], float("-inf"), float("inf")))


def of_run(run):
    """(all program spans, the traced window) of a traced run; None
    when the run was not traced or the program wrote no span (a program
    without them reads no metric)."""
    if run.profile is None:
        return None
    spans = program_spans(run.profile)
    if not spans:
        return None
    return spans, run.trace_window


def named(spans: list, window: tuple, name: str) -> list:
    """The spans called ``name`` that start in the window."""
    lo, hi = window
    return [s for s in spans if s.name == name and lo <= s.start < hi]


def mean_ms(values: list):
    """Mean of durations in ns, in ms; None for none."""
    return 1e-6 * sum(values) / len(values) if values else None


def per_call_ms(spans: list, window: tuple, call: str, parts: tuple):
    """Mean, over the ``call`` spans that start in the window, of the
    time their children named in ``parts`` cover (ms), each child counted
    with the call it runs in, wherever it falls; None without a call."""
    tree = Tree(spans)
    return mean_ms([covered_ns(tree.children(c, parts))
                    for c in named(spans, window, call)])
