"""Scheduler: p95 over the requests due inside the window of their
``queued`` span in the engine's Tracer (submit to admission into a
prefill group). A ring that dropped events fails the run."""

import numpy as np


def read(run):
    tr = run.tracer
    if tr is None:
        return None
    if tr.dropped:
        raise RuntimeError(f"the engine's trace ring dropped {tr.dropped} "
                           "events")
    want = {r.rid for r in run.records if r.item.in_window}
    begin, waits = {}, []
    for ev in tr.events:
        if ev.name != "queued" or ev.tid - 1 not in want:
            continue
        if ev.ph == "B":
            begin[ev.tid] = ev.ts_us
        elif ev.ph == "E" and ev.tid in begin:
            waits.append((ev.ts_us - begin.pop(ev.tid)) * 1e-3)
    return float(np.percentile(waits, 95)) if waits else None
