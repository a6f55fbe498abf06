"""Device time of one launch of the engine's decode program (the
launched programs inside which ``tl_decode_glue`` ran: the program gives
its jitted functions no name of their own), median over the traced
window. A per-layer statistic; the end-to-end rate is what a stall
moves."""

import statistics


def read(run):
    launches = run["trace"].modules_holding("tl_decode_glue")
    if not launches:
        return None
    return statistics.median(m.dur for m in launches) / 1e6
