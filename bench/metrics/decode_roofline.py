"""Kernels: the decode executables' share of their roofline. The least
time is the larger of their FLOPs over the bf16 peak and their least
bytes over the HBM bandwidth (bench/counts.py, from the lengths served
in the traced window); the share is that over their device time."""


def read(run):
    dec = run.device_trace["modules"]["decode"]
    if not dec["runs"] or dec["seconds"] <= 0:
        return None
    flops, nbytes = run.decode_counts()
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return least / dec["seconds"] * 100.0
