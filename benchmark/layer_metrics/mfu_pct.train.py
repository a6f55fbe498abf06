"""The whole training step's share of the chip's published bf16 peak:
forward and backward FLOPs a token (no recomputation counted) times the
tokens of the steps that ended in the (untraced part of the) window."""

import importlib


def read(run):
    cfg, c = run["config"], run["counters"]
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    if not c.get("steps"):
        return None
    flops = (
        fam.train_flops_per_token(cfg, c["seq_len"])
        * c["steps"] * c["tokens_per_step"]
    )
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / (c["counter_window_s"] * peak)
