"""99th percentile of (start of the pipeline call that carried an
open-loop request - the time it was due): the wait in and before
``MicroBatchQueue``."""

import numpy as np


def read(run):
    req = run.requests
    if not req or not req["served"].any():
        return None
    wait = (req["start"] - req["due"])[req["served"]]
    return 1e3 * float(np.percentile(wait, 99))
