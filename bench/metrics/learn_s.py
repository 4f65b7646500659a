"""``Index.learn_seconds``: the mechanism-learning share of the build
(sampling, fit, refit)."""


def read(run):
    return run.learn_s
