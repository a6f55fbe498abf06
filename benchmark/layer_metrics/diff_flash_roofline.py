"""The flash-attention kernels' share of their roofline in the
differential-attention layers of a training step: the least time the
needed work could take over the device time the kernels took, in the
traced window. Needed, a step: each attention layer makes two calls (a
pair's two score maps), each one forward, one ``bwd_dq``, one
``bwd_dkv`` at q, k ``d`` and v ``2d`` wide, H/2 query heads on Hkv/2
key heads; a window layer's calls over the band of ``sliding_window``
keys, the others over half the score square
(``kernels/tl_flash_diff.py``). Executed may be more: a rematerialised
block runs the forward a second time, and a band is cut in whole blocks;
those calls' time is in the denominator with no work beside it. Steps
are counted by ``bwd_dkv`` calls, two a step and attention layer."""

from benchmark import roofline
from benchmark.families import phi4flash
from benchmark.kernels import tl_flash_diff


def read(run):
    tr, cfg, mix = run["trace"], run["config"], run["mix"]
    if "published_num_hidden_layers" not in cfg:
        return None
    kinds = phi4flash.layer_kinds(cfg)
    layers = {  # window (None: all earlier keys) -> attention layers
        cfg["sliding_window"]: kinds["window"],
        None: kinds["full"] + kinds["cross"],
    }
    calls_a_step = 2 * sum(layers.values())
    if not calls_a_step:
        return None
    rows = mix["batch_size"] // mix["micro_batches"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    steps = len(tr.kernel_events("tl_flash_bwd_dkv")) / calls_a_step
    least = spent = 0.0
    for kernel in tl_flash_diff.MATMULS:
        calls = tr.kernel_events(kernel)
        if not calls:
            return None
        for window, n in layers.items():
            f, b = tl_flash_diff.work(
                kernel, rows, heads // 2, mix["seq_len"], d, 2 * d,
                window=window, kv_heads=kv_heads // 2,
            )
            least += steps * 2 * n * roofline.least_seconds(
                f, b, run["peaks"])[0]
        spent += sum(e.dur for e in calls) / 1e9
    return 100.0 * least / spent
