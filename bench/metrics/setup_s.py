"""Seconds from the process's start to the window's: imports, weights
drawn and quantized, every shape compiled or loaded and run once, and
the traffic's own warm-up."""


def read(run):
    return run.setup_s
