"""p95 over all requests due inside the window of the time from when
each was due to its first token on the host. A request with no token
when the run ended counts with the run's end as its first token."""

import numpy as np


def read(run):
    waits = [(r.times[0] if r.times else run.t_end) - r.due
             for r in run.due_in_window()]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
