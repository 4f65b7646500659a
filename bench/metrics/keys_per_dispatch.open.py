"""Mean real (unpadded) keys per lookup call that served open-loop
requests."""

import numpy as np


def read(run):
    req = run.requests
    if not req:
        return None
    return float(np.mean(req["call_keys"]))
