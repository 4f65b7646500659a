"""99th percentile, over every open-loop request due in the window, of the
end of the pipeline call that answered it minus the time it was due."""


def read(run):
    return run.open_latency_ms(99)
