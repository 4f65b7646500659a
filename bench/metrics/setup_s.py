"""Seconds from process start to the first measured request."""


def read(run):
    return run.setup_s
