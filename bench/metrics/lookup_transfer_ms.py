"""Time (ms) the host thread of ``Index.lookup`` spends in the TPU
runtime moving data: the mean, over the ``repro.index.lookup`` spans that
start in the traced window, of what their ``repro.engine.put`` (queries
to the device) and ``repro.engine.fetch`` (the wait for the device's run
and the outputs' copy to the host) children cover.  The reader of
``lookup_transfer_ms.open`` and ``lookup_transfer_ms.bulk``."""

from bench import spans


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    return spans.per_call_ms(*got, "repro.index.lookup",
                             ("repro.engine.put", "repro.engine.fetch"))
