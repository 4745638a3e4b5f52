"""Output tokens delivered to the host inside the window, per second of
the window (host clock; every request's tokens count, whenever it was
due). The window opens and closes as the load generator meets its
nominal edges, each at the end of a scheduler round: the program
delivers a round's tokens at once, so a window cut inside a round would
count its cost in full and its tokens as chance put them on either
side."""


def read(run):
    t0, t1 = run.t_open, run.t_close
    n = sum(sum(t0 <= t < t1 for t in r.times) for r in run.records)
    return n / (t1 - t0)
