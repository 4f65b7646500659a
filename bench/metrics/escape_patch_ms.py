"""Host escape patch (ms) per ``Index.lookup`` call: the mean, over the
``repro.index.lookup`` spans that start in the traced window, of the
time their ``repro.engine.escape_patch`` children cover (the rebuild of
its host copy, ``repro.engine.host_views``, nests inside).  The reader
of ``escape_patch_ms.bulk`` and ``escape_patch_ms.load``."""

from bench import spans


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    return spans.per_call_ms(*got, "repro.index.lookup",
                             ("repro.engine.escape_patch",))
