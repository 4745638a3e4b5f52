"""Load generator: p95 over the requests due inside the window of how
late each was submitted (submit time minus due time). The generator
submits between scheduler rounds, so this is how long a round held it."""

import numpy as np


def read(run):
    late = [r.submit - r.due for r in run.due_in_window()
            if r.submit is not None]
    return float(np.percentile(late, 95)) * 1e3 if late else None
