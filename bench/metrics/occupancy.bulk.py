"""Scheduler: the engine's occupancy over the window (active slot-steps
over dispatched slot-steps, ``EngineMetrics.occupancy``), in percent."""


def read(run):
    m = run.engine_window
    return m.occupancy * 100.0 if m.decode_steps else None
