"""Whole decode step: model FLOPs of the tokens decoded in the traced
window (bench/counts.py) over the decode executables' device time,
as a share of the chip's bf16 peak."""


def read(run):
    dec = run.device_trace["modules"]["decode"]
    if not dec["runs"] or dec["seconds"] <= 0:
        return None
    flops, _ = run.decode_counts()
    return flops / dec["seconds"] / run.peaks["bf16_flops_per_s"] * 100.0
