"""The flash-attention kernels' share of their roofline in the MLA
layers of a training step: the least time the needed work could take
over the device time the kernels took, in the traced window. Needed, a
step and an MLA layer: one forward, one ``bwd_dq``, one ``bwd_dkv`` at
q, k 192 and v 128 wide, half the score square
(``kernels/tl_flash_mla.py``). Executed may be more: a rematerialised
block runs the forward a second time, and that call's time is in the
denominator with no work beside it. Steps are counted by ``bwd_dkv``
calls, one a step and layer."""

from benchmark import roofline
from benchmark.kernels import tl_flash_mla


def read(run):
    tr, cfg, mix = run["trace"], run["config"], run["mix"]
    if "qk_nope_head_dim" not in cfg:
        return None
    rows = mix["batch_size"] // mix["micro_batches"]
    steps = len(tr.kernel_events("tl_flash_bwd_dkv"))
    least = spent = 0.0
    for kernel in tl_flash_mla.MATMULS:
        calls = tr.kernel_events(kernel)
        if not calls:
            return None
        f, b = tl_flash_mla.work(
            kernel, rows, cfg["num_attention_heads"], mix["seq_len"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"],
        )
        least += steps * roofline.least_seconds(f, b, run["peaks"])[0]
        spent += sum(e.dur for e in calls) / 1e9
    return 100.0 * least / spent
