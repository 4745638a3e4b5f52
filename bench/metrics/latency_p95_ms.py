"""p95 over all requests due inside the window of the time from when
each was due to its last token on the host: the whole sentence's wait.
A request unfinished when the run ended counts with the run's end."""

import numpy as np


def read(run):
    waits = [(r.times[-1] if r.done and r.times else run.t_end) - r.due
             for r in run.due_in_window()]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
