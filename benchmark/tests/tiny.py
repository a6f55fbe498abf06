"""Tiny cells for the CPU rehearsal: the same drivers, families,
references, generator and readers as the chip runs, at sizes a test
holds. Never a switch of the command: tests call the drivers."""

from __future__ import annotations

import json
import re
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def bench_with_serving() -> dict:
    """BENCHMARK.json with the entries of the serving cell that the
    README keeps for the PR that adds it (bounds not set yet)."""
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    block = re.search(
        r"```json\n(\{.*?\n\})\n```", (HERE / "README.md").read_text(), re.S
    ).group(1)
    more = json.loads(block.replace('"<from the measured spread>"', "0.1"))
    have = {w["name"] for w in bench["workloads"]}
    if more["workloads"][0]["name"] in have:  # the cell has been added
        return bench
    return {
        k: bench[k] + more[k] if k in more else bench[k] for k in bench
    }


LLAMA_TINY = {
    "family": "llama", "hidden_size": 32, "intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "num_hidden_layers": 2, "vocab_size": 128,
    "max_position_embeddings": 64, "sliding_window": 8,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "engine": {
        "kind": "paged", "slots": 4, "block_size": 4, "max_len": 64,
        "decode_chunk": 4, "pipeline_depth": 2, "prefill_chunk": 8,
        "prefix_cache": True, "sampling": "greedy",
    },
}
SERVE_TINY = {
    "driver": "serve_engine", "loop": "closed", "clients": 6,
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 3, "max": 16},
    "distinct_sizes": 8, "ids": "uniform",
    "sampling": "greedy", "eos": None, "trace_seconds": 1,
    "check": {"requests": 4},
}
GPT2_TINY = {
    "family": "gpt2", "n_layer": 2, "n_embd": 32, "n_head": 2,
    "n_positions": 64, "vocab_size": 128, "layer_norm_epsilon": 1e-5,
    "train": {
        "optimizer": "adam", "learning_rate": 2e-6, "b1": 0.9, "b2": 0.999,
        "eps": 1e-8, "clip_norm": 1.0, "weight_decay": 0.0,
        "schedule": "constant", "warmup_steps": 0,
        "compute_dtype": "bfloat16",
    },
}
TRAIN_TINY = {
    "driver": "train", "seq_len": 32, "batch_size": 8, "micro_batches": 2,
    "ids": "uniform", "trace_seconds": 1,
    "check": {"steps": 3, "rows_per_block": 4},
}


def cell(config, mix, limits, seed=7, seconds=1.0, trace=False, tracedir=None,
         **more):
    return types.SimpleNamespace(
        name="tiny", config=config, mix=mix, limits=limits, seed=seed,
        seconds=seconds, trace=trace, chips=1,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 16e9},
        tracedir=tracedir, t_start=time.perf_counter(), **more,
    )

# limits at the tiny sizes, set as the chip's are (PERF.md section 2):
# above what the program reads on seeds 1-7 here (loss 3.6e-5, global
# norm 0.002, leaf gradient 0.018, leaf change 0.028; served gap 0.0 to
# 0.035 over 16 requests of seeds 6-13),
# below what the fp8 control reads on seeds 1-2 (8.5e-5, 0.0034, 0.032,
# 0.047; served gap 0.31 to 0.94 over 16 requests)
SERVE_LIMITS = {"served_logit_gap": 0.1}
TRAIN_LIMITS = {
    "loss_gap": 7e-5, "global_norm_gap": 0.003, "grad_norm_gap": 0.025,
    "delta_norm_gap": 0.038,
}
