"""Roofline arithmetic (copied from ``runtime/profiling.py::roofline``,
whose arithmetic is sound; the peaks here are the published ones)."""


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
