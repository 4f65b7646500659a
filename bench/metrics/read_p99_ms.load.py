"""99th percentile, over the closed-loop reads sent in the window while
the loader streams inserts, of (answer - send) on the reader's clock."""

import numpy as np


def read(run):
    if not run.reads:
        return None
    return 1e3 * float(np.percentile([r[2] - r[1] for r in run.reads], 99))
