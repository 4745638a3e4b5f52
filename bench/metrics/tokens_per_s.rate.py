"""tokens_per_s in a cell whose offered load sits below its knee: there
the tokens delivered follow the load offered, so it is no end-to-end
metric (the tails are), and it is read beside them as a check on the
load generator."""


def read(run):
    return run.metric("tokens_per_s")
