"""Self time (ms) of ``MicroBatchQueue.flush`` under the queue's lock:
the mean, over the ``repro.queue.flush`` spans that start in the traced
window, of each span's duration less what its ``repro.pipeline.*``
children cover.  What is left is staging, the per-ticket demux and
bookkeeping.  The reader of ``flush_self_ms.open``."""

from bench import spans


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    all_, window = got
    tree = spans.Tree(all_)
    return spans.mean_ms([
        (p.end - p.start)
        - spans.covered_ns(tree.children(p, prefix="repro.pipeline."))
        for p in spans.named(all_, window, "repro.queue.flush")])
