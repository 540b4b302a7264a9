"""95th percentile, over every decision applied in the window, of the time
from its round's start to the return of the lane's ``apply``."""
import numpy as np


def read(run):
    if not run.latencies:
        return None
    return 1e3 * float(np.quantile(np.asarray(run.latencies), 0.95))
