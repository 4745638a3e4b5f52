"""Cache: the page pool's mean use over the window's decode steps
(``EngineMetrics.page_utilization``), in percent."""


def read(run):
    m = run.engine_window
    return m.page_utilization * 100.0 if m.decode_steps else None
