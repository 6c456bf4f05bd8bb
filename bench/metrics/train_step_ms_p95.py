"""train_step_ms_p95: 95th percentile (linear interpolation) of the wall
time of the window's steps.  Steps are queued ahead on the chips, so a
step's time runs from the end of the step before it (from the copy of
its inputs for the first) to the end of its own block_until_ready.  Host
clock."""

import numpy as np


def read(rec):
    if not rec.window:
        return None
    return float(np.percentile([r["s"] * 1e3 for r in rec.window], 95))
