"""Host partition and CSR merge (ms) per ``Index.ingest``: the mean,
over the ``repro.index.ingest`` spans that start in the traced window,
of the time their ``repro.index.insert`` children cover."""

from bench import spans


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    return spans.per_call_ms(*got, "repro.index.ingest",
                             ("repro.index.insert",))
