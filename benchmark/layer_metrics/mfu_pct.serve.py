"""The whole served step's share of the chip's published bf16 peak:
the FLOPs the requests that finished in the (untraced part of the)
window needed -- matmuls at 2 x parameters touched for every prompt and
output token, attention over the live context of each -- over that
window's length."""

import importlib


def read(run):
    cfg, c = run["config"], run["counters"]
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    if not c.get("finished"):
        return None
    flops = 0.0
    for p, n in c["finished"]:
        flops += 2.0 * fam.matmul_params(cfg) * (p + n)
        # token i (from 1) attends i keys, prompt and output alike
        total = p + n
        flops += sum(
            fam.attn_flops(cfg, i) for i in (1, total)
        ) * total / 2.0
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / (c["counter_window_s"] * peak)
