"""The three flash-attention kernels' share of their roofline in the
training step: summed least time over summed device time of
``tl_flash_fwd``, ``tl_flash_bwd_dq`` and ``tl_flash_bwd_dkv`` calls in
the traced window. One call is one micro-batch of one layer."""

from benchmark import roofline
from benchmark.kernels import tl_flash


def read(run):
    tr, cfg, mix = run["trace"], run["config"], run["mix"]
    rows = mix["batch_size"] // mix["micro_batches"]
    heads = cfg["n_head"]
    least = spent = 0.0
    for kernel in tl_flash.MATMULS:
        calls = tr.kernel_events(kernel)
        if not calls:
            return None
        f, b = tl_flash.work(
            kernel, rows, heads, mix["seq_len"], cfg["n_embd"] // heads
        )
        least += len(calls) * roofline.least_seconds(f, b, run["peaks"])[0]
        spent += sum(e.dur for e in calls) / 1e9
    return 100.0 * least / spent
