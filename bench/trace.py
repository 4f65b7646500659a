"""Reduction of a profiler trace to device busy time, module time and
idle gaps named by the benchmark's host spans.

``load_profile`` turns the ``.xplane.pb`` the JAX profiler writes into a
plain dict ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``; every other function works on that
dict, so a small recorded trace in that form tests the reduction.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.window"   # the traced window, opened by the harness
SPAN_PREFIX = "bench."         # host spans the harness writes
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 160               # an op's name is its HLO text: keep its head


def load_profile(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        lines = [{"name": line.name,
                  "events": [[ev.name, float(ev.start_ns),
                              float(ev.duration_ns)] for ev in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# a chip's plane: ``/device:TPU:0``.  Other ``/device:`` planes, such
# as the runtime's ``Megascale Trace``, hold no chip's ops.
CHIP_PLANE = re.compile(r"/device:(?!CPU:)[A-Za-z_]+:(\d+)")


def _chip(plane: dict) -> int | None:
    m = CHIP_PLANE.fullmatch(plane["name"])
    return int(m.group(1)) if m else None


def device_planes(profile: dict) -> list:
    """The planes of chips: one per device id."""
    return [p for p in profile["planes"] if _chip(p) is not None]


def keep_devices(profile: dict, ids) -> dict:
    """``profile`` without the planes of chips outside ``ids``, so every
    reduction reads only the chips a run used."""
    ids = {int(i) for i in ids}
    return {"planes": [p for p in profile["planes"]
                       if _chip(p) is None or _chip(p) in ids]}


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(profile: dict) -> list:
    """``[name, start_ns, end_ns]`` of every benchmark span on the host."""
    out = []
    for plane in profile["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append([name, start, start + dur])
    return out


def traced_window(profile: dict) -> tuple:
    """(start_ns, end_ns) of the harness's window span."""
    for name, start, end in host_spans(profile):
        if name == WINDOW_SPAN:
            return start, end
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` intervals, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                if e > lo and s < hi)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _ops(plane: dict) -> list:
    return [(s, s + d) for _, s, d in _line(plane, OPS_LINE)]


def busy_seconds(profile: dict, window: tuple) -> float:
    """Seconds in which an operation ran on the device, as the union of
    op intervals in the window, averaged over the device planes."""
    planes = device_planes(profile)
    if not planes:
        return 0.0
    lo, hi = window
    tot = sum(e - s for p in planes for s, e in union(_ops(p), lo, hi))
    return tot / len(planes) / 1e9


def module_time(profile: dict, prefix: str, window: tuple) -> tuple:
    """(seconds, executions) of the modules whose name starts with
    ``prefix``, summed over device planes, inside the window."""
    lo, hi = window
    secs, n = 0.0, 0
    for p in device_planes(profile):
        for name, s, d in _line(p, MODULES_LINE):
            if name.startswith(prefix) and s >= lo and s + d <= hi:
                secs += d / 1e9
                n += 1
    return secs, n


def top_ops(profile: dict, window: tuple, k: int = 10) -> list:
    """The ``k`` device ops that took most time in the window."""
    lo, hi = window
    acc: dict = {}
    for p in device_planes(profile):
        for name, s, d in _line(p, OPS_LINE):
            if s >= lo and s < hi:
                name = name[:NAME_CHARS]
                acc[name] = acc.get(name, 0.0) + d / 1e9
    return sorted(([n, v] for n, v in acc.items()), key=lambda r: -r[1])[:k]


def idle_gaps(profile: dict, window: tuple, k: int = 10) -> list:
    """Device idle time in the window, summed by the innermost benchmark
    span open on the host at each gap's midpoint; ``k`` largest."""
    planes = device_planes(profile)
    if not planes:
        return []
    lo, hi = window
    spans = sorted((s for s in host_spans(profile) if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0.0)
    acc: dict = {}
    for p in planes:
        busy = union(_ops(p), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            # only spans starting within ``longest`` before mid can be open
            i0 = bisect.bisect_left(starts, mid - longest)
            i1 = bisect.bisect_right(starts, mid)
            open_ = [s for s in spans[i0:i1] if mid < s[2]]
            label = (min(open_, key=lambda s: s[2] - s[1])[0] if open_
                     else "no benchmark span (generator, queue wait)")
            acc[label] = acc.get(label, 0.0) + (b - a) / 1e9 / len(planes)
    return sorted(([n, v] for n, v in acc.items()), key=lambda r: -r[1])[:k]


def trim(profile: dict, window: tuple, keep_lines=(OPS_LINE, MODULES_LINE)
         ) -> dict:
    """A smaller copy: the benchmark's spans and the device lines named in
    ``keep_lines``, restricted to events that overlap the window, names
    cut to ``NAME_CHARS``."""
    lo, hi = window
    planes = []
    for p in profile["planes"]:
        dev = p["name"].startswith("/device:")
        lines = []
        for line in p["lines"]:
            if dev and line["name"] not in keep_lines:
                continue
            evs = [[e[0][:NAME_CHARS], e[1], e[2]] for e in line["events"]
                   if e[1] + e[2] >= lo and e[1] <= hi
                   and (dev or e[0].startswith(SPAN_PREFIX))]
            if evs:
                lines.append({"name": line["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
