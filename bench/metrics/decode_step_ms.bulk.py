"""decode_step_ms, in a cell where it moves tokens_per_s."""


def read(run):
    return run.metric("decode_step_ms")
