"""Host self time (ms) of ``Index.lookup``: the mean, over the
``repro.index.lookup`` spans that start in the traced window, of each
span's duration less what its ``repro.engine.put``, ``repro.engine.fetch``
and ``repro.engine.escape_patch`` children cover.  What is left is host
work: sync check, query split, bucket and pad, the dispatch's enqueue,
and the result's assembly.  The reader of ``lookup_host_ms.open`` and
``lookup_host_ms.bulk``."""

from bench import spans

AWAY = ("repro.engine.put", "repro.engine.fetch",
        "repro.engine.escape_patch")


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    all_, window = got
    tree = spans.Tree(all_)
    return spans.mean_ms([
        (p.end - p.start) - spans.covered_ns(tree.children(p, AWAY))
        for p in spans.named(all_, window, "repro.index.lookup")])
