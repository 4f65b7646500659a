"""1 - (union of device-op intervals / traced window).  The reader of
``device_idle_share.open``, ``.bulk`` and ``.load``: a split name with no
file of its own falls back to its base."""

from bench import trace


def read(run):
    if run.profile is None:
        return None
    lo, hi = run.trace_window
    return 1.0 - trace.busy_seconds(run.profile, run.trace_window) / (
        (hi - lo) / 1e9)
