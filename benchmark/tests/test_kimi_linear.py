"""The Kimi-Linear family through the benchmark's own driver at a tiny
size on the CPU: the program against ``reference/kimi_linear.py``, the
fp8 control and the half-batch fault against the same limits, the
counts of the family file, and the cell's files."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness, weights
from benchmark.drivers import train
from benchmark.families import kimi_linear as fam
from benchmark.tests import tiny

KIMI_TINY = {
    "family": "kimi_linear", "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "vocab_size": 128,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 2,
        "head_dim": 16, "short_conv_kernel_size": 4,
    },
    "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "moe_intermediate_size": 16,
    "num_experts": 4, "num_experts_per_token": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "rms_norm_eps": 1e-5,
    "deployment_share": {"router_width": 16, "first_expert": 4},
    # float32 compute: at these widths (heads of 16, 512 tokens) a bf16
    # step's gradient stands a third off the reference's, and so does
    # the reference's own with its weights rounded to bf16 (PERF.md,
    # PR 29), so bf16 tells nothing here; at the cell's size on the chip
    # it stands 5e-4 off. The plumbing is what this rehearses.
    "train": dict(
        tiny.GPT2_TINY["train"], compute_dtype="float32", remat=True,
        moe_row_bound=None,
    ),
}
KIMI_MIX = {
    "driver": "train", "seq_len": 128, "batch_size": 4, "micro_batches": 1,
    "ids": "uniform", "trace_seconds": 1,
    "check": {"steps": 3, "rows_per_block": 1},
}
# the float32 program reads 1e-6 / 2e-5 / 4e-4 / 6e-4 on seeds 3-5 here, the
# fp8 control 8e-3 / 0.29 / 2.1 / 0.056, the half batch 8e-3 / 0.41 / 0.35 / 0.06
LIMITS = {
    "loss_gap": 1e-4, "global_norm_gap": 0.01, "grad_norm_gap": 0.02,
    "delta_norm_gap": 0.01,
}


def _run(seed, **kw):
    cell = tiny.cell(KIMI_TINY, KIMI_MIX, LIMITS, seed=seed, seconds=0.3, **kw)
    return cell, train.run(cell)


def test_train_runs_and_is_correct():
    cell, res = _run(3)
    ok, checks = harness.decide(res["checks"])
    assert ok, checks
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["end_to_end"]) == {"train_tok_per_s", "setup_s"}


@pytest.mark.parametrize("mode", ["fp8", "half_batch"])
def test_control_is_not_correct(mode):
    _, res = _run(4, control_modes=(mode,))
    ok, _ = harness.decide(harness.against(res["controls"][mode], LIMITS))
    assert not ok


def test_every_leaf_has_a_rule_and_a_live_gradient():
    """``weights.make_leaf`` knows every leaf's name, and the reference's
    gradient of every leaf but the selection bias is not nought."""
    model = fam.build(KIMI_TINY)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    ref = train.reference_run(fam, KIMI_TINY, KIMI_MIX, 5, shapes, 1)
    dead = {n for n, g in ref["grad"].items() if g == 0.0}
    assert dead == {f"blocks/{i}/mlp/router/bias" for i in (1, 2, 3, 4)}
    assert all(np.isfinite(g) for g in ref["grad"].values())
    params = weights.make_tree(5, shapes)
    assert len(jax.tree.leaves(params)) == len(ref["grad"])


def test_counts_at_the_cell_size():
    cfg = json.loads(
        (harness.HERE / "configs" / "kimi-linear-l5e8.json").read_text()
    )
    model = fam.build(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert round(held / 1e6, 1) == 602.4  # 16 B each: 9.6 GB of state
    # a token meets every weight but the table and 8 - 8 * 8 / 256 of
    # the held experts; the router's bias and the norms are no matmul
    per_token = fam.matmul_params(cfg)
    assert 0.55 * held < per_token < 0.56 * held
    flops = fam.train_flops_per_token(cfg, 4096)
    assert round(flops / 1e9, 2) == 2.18
    # MLA at 4,096: a tenth of the forward; the recurrence 1.5 %
    mla = 3 * 2.0 * 32 * (192 + 128) * 2048.5
    assert 0.05 < mla / flops < 0.06
    assert fam.attn_flops(cfg, 0) == 4 * 7.0 * 32 * 128 ** 2


def test_the_cell_is_whole():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = next(
        w for w in bench["workloads"]
        if w["name"] == "kimi-linear-l5e8.train_lm_s4096"
    )
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((harness.ROOT / conf["file"]).read_text())
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    mix = json.loads(
        (harness.HERE / "traffic" / f"{cell['traffic']}.json").read_text()
    )
    assert (mix["seq_len"], mix["batch_size"], mix["micro_batches"]) == (
        4096, 4, 1)
    limits = json.loads(
        (harness.HERE / "limits" / f"{cell['name']}.json").read_text()
    )
    assert set(limits["limits"]) == set(LIMITS)
    for m in bench["per_layer"]:
        if cell["name"] in m.get("workloads", ()):
            assert m["moves"] == "train_tok_per_s"


# ------------------------------------------------- readers, on a recording
# kimi_2l.xplane.pb: recorded on a TPU v5e in PR 29 through the train
# driver: two layers at the cell's widths (KDA + dense, MLA + experts),
# 2 x 1,024 tokens a step, blocks rematerialised.
NEW_READERS = {
    "kda_device_ms.train": ("tl.kda",),
    "kda_scan_device_ms.train": ("tl.kda.scan",),
    "mla_device_ms.train": ("tl.mla",),
    "moe_device_ms.train": ("tl.moe",),
    "moe_experts_device_ms.train": ("tl.moe.experts",),
}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from benchmark import trace
    from benchmark.tests.test_trace import _unpack

    path = _unpack("kimi_2l.xplane.pb", tmp_path_factory.mktemp("k"))
    cfg = json.loads(
        (harness.HERE / "configs" / "kimi-linear-l5e8.json").read_text())
    mix = json.loads(
        (harness.HERE / "traffic" / "train_lm_s4096.json").read_text())
    mix.update(seq_len=1024, batch_size=2)
    return {
        "tracedir": path, "trace": trace.reduce(path), "config": cfg,
        "mix": mix, "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
    }


def _read(name, run):
    import benchmark.run as runner

    return runner.load_reader(name).read(run)


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_scope_reader_on_the_recording(recorded, name):
    value = _read(name, recorded)
    assert value is not None and value > 0


def test_the_groups_add_up_to_the_step(recorded):
    """Scoped groups + head and loss + update + unscoped are the step:
    nothing of a Kimi block lies outside the new readers and tl.mlp.
    Each number is a median over the recording's 42 launches, and
    medians add up only nearly (2e-5 here)."""
    from benchmark import scope_ms, spans

    split = spans.step_split(recorded)
    parts = sum(_read(n, recorded) for n in (
        "kda_device_ms.train", "mla_device_ms.train", "moe_device_ms.train",
        "mlp_device_ms.train", "head_loss_device_ms.train",
        "update_device_ms.train",
    ))
    unscoped = (split["total"] - split["scoped"]) / 1e6
    # the grouped matmuls carry no op path: the two moe readers count
    # them by name, scoped_device_pct.train as unscoped
    grouped = _read("moe_experts_device_ms.train", recorded) - scope_ms.read(
        recorded, "tl.moe.experts")
    assert 0 < grouped < unscoped
    assert parts + unscoped - grouped == pytest.approx(
        split["total"] / 1e6, rel=1e-4)
    # what spans.GROUPS calls "other" is exactly the new scopes
    new = sum(_read(n, recorded) for n in (
        "kda_device_ms.train", "mla_device_ms.train", "moe_device_ms.train"))
    assert new - grouped == pytest.approx(split["other"] / 1e6, rel=1e-4)
    assert _read("kda_scan_device_ms.train", recorded) < _read(
        "kda_device_ms.train", recorded)
    assert _read("moe_experts_device_ms.train", recorded) < _read(
        "moe_device_ms.train", recorded)
    assert 0 < _read("scoped_device_pct.train", recorded) <= 100


def test_mla_flash_roofline_on_the_recording(recorded):
    from benchmark import roofline
    from benchmark.kernels import tl_flash_mla

    value = _read("mla_flash_roofline", recorded)
    assert 0 < value < 100
    tr = recorded["trace"]
    steps = len(tr.kernel_events("tl_flash_bwd_dkv"))
    # a rematerialised block runs the forward twice a step
    assert len(tr.kernel_events("tl_flash_fwd")) == 2 * steps
    least = sum(
        roofline.least_seconds(
            *tl_flash_mla.work(k, 2, 32, 1024, 192, 128), recorded["peaks"]
        )[0] for k in tl_flash_mla.MATMULS
    )
    spent = sum(
        e.dur for k in tl_flash_mla.MATMULS for e in tr.kernel_events(k)
    ) / 1e9
    assert value == pytest.approx(100 * steps * least / spent)


def test_new_readers_find_nothing_in_an_older_program(tmp_path):
    """GPT-2's recording has none of the scopes and no MLA widths: every
    new reader returns None (the line leaves the metric out), never 0."""
    from benchmark import trace
    from benchmark.tests.test_trace import _unpack

    path = _unpack("train_2l_scoped.xplane.pb", tmp_path)
    cfg = json.loads((harness.HERE / "configs" / "gpt2-medium.json").read_text())
    run = {
        "tracedir": path, "trace": trace.reduce(path), "config": cfg,
        "mix": {"batch_size": 4, "micro_batches": 2, "seq_len": 1024},
        "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
    }
    for name in (*NEW_READERS, "mla_flash_roofline"):
        assert _read(name, run) is None
    assert _read("mlp_device_ms.train", run) > 0  # the trace is read


def test_flash_mla_counts():
    from benchmark.kernels import tl_flash, tl_flash_mla

    # at equal widths the count is tl_flash's own
    for k in tl_flash.MATMULS:
        assert tl_flash_mla.work(k, 4, 16, 1024, 64, 64) == tl_flash.work(
            k, 4, 16, 1024, 64)
    f, b = tl_flash_mla.work("tl_flash_fwd", 4, 32, 4096, 192, 128)
    pairs = 4 * 32 * 4096 * 4096 / 2
    assert f == 2 * (192 + 128) * pairs
    assert b == 4 * 32 * 4096 * (2 * 192 + 2 * 128) * 2
    f, _ = tl_flash_mla.work("tl_flash_bwd_dkv", 4, 32, 4096, 192, 128)
    assert f == 2 * (2 * 192 + 2 * 128) * pairs
