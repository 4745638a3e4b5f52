"""Scheduler: the share of the traced window in which no operation ran
on the chip while the host was inside the engine's ``admit`` phase (its
Tracer's ``admit`` spans on the scheduler track, laid on the profiler's
clock through the ``bench.mark`` annotation the harness emits at the
window's start), in percent."""

from bench.layers import overlap_s


def read(run):
    dt, tr = run.device_trace, run.tracer
    window = run.trace_t1 - run.trace_t0
    if tr is None or dt["mark_ns"] is None or not dt["chips_seen"] \
            or window <= 0:
        return None
    # the host's perf_counter at the mark, and its offset to the profile
    mark_perf = run.origin + run.trace_t0
    shift = dt["mark_ns"] - mark_perf * 1e9
    admit = [(ev.ts_us * 1e3 + shift, (ev.ts_us + ev.dur_us) * 1e3 + shift)
             for ev in tr.events
             if ev.ph == "X" and ev.tid == 0 and ev.name == "admit"]
    if not admit:
        return None
    return overlap_s(dt["gaps_ns"], admit) / window * 100.0
