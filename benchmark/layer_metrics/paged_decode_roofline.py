"""``tl_paged_decode``'s share of its roofline over the traced window:
summed least time over summed device time of its calls.

Work per call comes from shapes and the traffic's live contexts (see
``benchmark/kernels/tl_paged_decode.py``). Calls inside the decode
program (the launched programs in which ``tl_decode_glue`` also ran)
serve the decoding rows, one query each; the mean context of a decode
step is taken over every output token of the requests that finished in
the window, the mean number of decoding rows from the engine's
``stats()`` sampled between steps of the traced window. The other calls
are prefill chunks: ``prefill_chunk`` queries of one row over the
prompt so far.
"""

import bisect

from benchmark import roofline
from benchmark.kernels import tl_paged_decode

KERNEL, DECODE_MARK = "tl_paged_decode", "tl_decode_glue"


def read(run):
    tr, c, cfg = run["trace"], run["counters"], run["config"]
    calls = tr.kernel_events(KERNEL)
    samples, finished = c.get("step_samples"), c.get("finished")
    if not calls or not samples or not finished or not tr.modules:
        return None
    decode = tr.modules_holding(DECODE_MARK)
    spans = sorted((m.start, m.end) for m in decode)

    def in_decode(e):
        i = bisect.bisect_right(spans, (e.start, float("inf"))) - 1
        return i >= 0 and e.start < spans[i][1]

    n_dec = sum(1 for e in calls if in_decode(e))
    n_pre = len(calls) - n_dec
    rows = sum(s["busy_slots"] - s["prefilling"] for s in samples) / len(samples)
    out_tokens = sum(n for _, n in finished)
    # an output token j (from 0) of a request with prompt p attends p + j + 1
    ctx_dec = sum(n * p + n * (n + 1) / 2 for p, n in finished) / out_tokens
    chunk = c["prefill_chunk"]
    offsets = [o for p, _ in finished for o in range(0, p, chunk)]
    ctx_pre = sum(o + chunk for o in offsets) / len(offsets)
    pairs_pre = sum(chunk * (o + (chunk + 1) / 2) for o in offsets) / len(offsets)
    least = 0.0
    f, b = tl_paged_decode.work(cfg, rows, rows * ctx_dec, rows * ctx_dec)
    least += n_dec * roofline.least_seconds(f, b, run["peaks"])[0]
    f, b = tl_paged_decode.work(cfg, chunk, ctx_pre, pairs_pre)
    least += n_pre * roofline.least_seconds(f, b, run["peaks"])[0]
    return 100.0 * least / (sum(e.dur for e in calls) / 1e9)
