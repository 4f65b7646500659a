"""Seconds from keys on the host to a servable device index:
``Index.build`` plus the first ``sync_device()``, taken in set-up."""


def read(run):
    return run.build_s
