"""Mean wall time (ms) of the pipeline's ``ingest`` call, publish
included, over the calls that started in the window."""


def read(run):
    return run.mean_ms(run.ingests)
