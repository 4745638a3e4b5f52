"""Model step: device time of the decode executables (the engine's
``_horizon`` scans) in the traced window, per decode micro-step they
ran (the engine's ``decode_steps`` over the same window)."""


def read(run):
    dec = run.device_trace["modules"]["decode"]
    if not dec["runs"] or not run.trace_steps:
        return None
    return dec["seconds"] / run.trace_steps * 1e3
