"""Device time of one launch of the ``Trainer`` step program (the
launched programs inside which ``tl_flash_bwd_dkv`` ran), median over
the traced window."""

import statistics


def read(run):
    launches = run["trace"].modules_holding("tl_flash_bwd_dkv")
    if not launches:
        return None
    return statistics.median(m.dur for m in launches) / 1e6
