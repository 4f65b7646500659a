"""Elements the device sync wrote (``IngestReport.device_elems``) per key
ingested, over the batches sent in the window."""


def read(run):
    b = [x for x in run.batches if run.window[0] <= x[2] < run.window[1]]
    keys = sum(x[1] for x in b)
    if not keys:
        return None
    return sum(x[4].device_elems for x in b) / keys
