"""The flash-attention kernels' share of their roofline in a Laguna
training step: the least time the needed work could take over the
device time the kernels took, in the traced window. Needed, a step and
attention layer: one forward, one ``bwd_dq``, one ``bwd_dkv`` at q, k
and v ``head_dim`` wide, at the layer's own query-head count on
``num_key_value_heads``; a ``sliding_attention`` layer's calls over the
band of ``sliding_window`` keys, the others over half the score square
(``kernels/tl_flash_diff.py``'s count with d_v = d_qk). Executed may be more: a rematerialised
block runs the forward a second time, and a band is cut in whole
blocks; those calls' time is in the denominator with no work beside it.
Steps are counted by ``bwd_dkv`` calls, one a step and attention
layer."""

from benchmark import roofline
from benchmark.kernels import tl_flash_diff


def read(run):
    tr, cfg, mix = run["trace"], run["config"], run["mix"]
    if "num_attention_heads_per_layer" not in cfg:
        return None
    layers = list(zip(cfg["num_attention_heads_per_layer"], cfg["layer_types"]))
    rows = mix["batch_size"] // mix["micro_batches"]
    steps = len(tr.kernel_events("tl_flash_bwd_dkv")) / len(layers)
    least = spent = 0.0
    for kernel in tl_flash_diff.MATMULS:
        calls = tr.kernel_events(kernel)
        if not calls:
            return None
        for heads, kind in layers:
            f, b = tl_flash_diff.work(
                kernel, rows, heads, mix["seq_len"], cfg["head_dim"],
                cfg["head_dim"], window=(cfg["sliding_window"]
                        if kind == "sliding_attention" else None),
                kv_heads=cfg["num_key_value_heads"],
            )
            least += steps * roofline.least_seconds(f, b, run["peaks"])[0]
        spent += sum(e.dur for e in calls) / 1e9
    return 100.0 * least / spent
