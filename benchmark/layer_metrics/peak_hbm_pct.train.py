"""Peak device memory after the window, as a share of the chip's HBM."""


def read(run):
    peak = run["counters"].get("memory_peak_bytes")
    return 100.0 * peak / run["peaks"]["hbm_bytes"] if peak else None
