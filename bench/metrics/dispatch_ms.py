"""Mean wall time (ms) of the pipeline's ``lookup`` call (host and
device), over the calls that started in the window.  The reader of
``dispatch_ms.open`` and ``dispatch_ms.bulk``."""


def read(run):
    return run.mean_ms(run.lookups)
