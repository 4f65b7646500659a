"""Host clock around the first ``Index.sync_device()``: the freeze to
the device."""


def read(run):
    return run.freeze_s
