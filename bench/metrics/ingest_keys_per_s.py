"""Keys acknowledged (ingest and publish returned) over the span from
the first batch's submit to the last batch's acknowledgement; only
batches sent in the window, each whole."""


def read(run):
    b = [x for x in run.batches if run.window[0] <= x[2] < run.window[1]]
    if not b:
        return None
    return sum(x[1] for x in b) / (max(x[3] for x in b) - min(x[2] for x in b))
