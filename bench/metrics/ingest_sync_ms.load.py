"""Device sync (ms) of an ingest: the mean, over the
``repro.index.ingest`` spans that start in the traced window and contain
a ``repro.index.sync``, of the time their sync children cover (the delta
diff and uploads, the bound refresh, or a refreeze)."""

from bench import spans


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    all_, window = got
    tree = spans.Tree(all_)
    syncs = [tree.children(p, ("repro.index.sync",))
             for p in spans.named(all_, window, "repro.index.ingest")]
    return spans.mean_ms([spans.covered_ns(c) for c in syncs if c])
