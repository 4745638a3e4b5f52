"""admit_idle_share, in a cell where it moves tokens_per_s."""


def read(run):
    return run.metric("admit_idle_share")
