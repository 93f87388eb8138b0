"""How late the load generator started a session's ingest after its due
time, ms: the 95th percentile over the window's sessions."""

import numpy as np


def read(run):
    xs = run.host.get("lag_s")
    return float(1e3 * np.percentile(xs, 95)) if xs else None
