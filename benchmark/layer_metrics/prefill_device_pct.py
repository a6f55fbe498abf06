"""Share of device-busy time spent in prefill-chunk launches (the
launched programs inside which ``tl_paged_decode`` ran and
``tl_decode_glue`` did not)."""


def read(run):
    tr = run["trace"]
    launches = tr.modules_holding("tl_paged_decode", without=("tl_decode_glue",))
    if not launches or not tr.busy_ns:
        return None
    return 100.0 * sum(m.dur for m in launches) / tr.busy_ns
