"""The Phi-4-mini-flash family through the benchmark's own driver at a
tiny size on the CPU: the program against ``reference/phi4flash.py``,
the fp8 control and the half-batch fault against the same limits, the
counts of the family file, the cell's files, and the four readers on a
synthetic trace of the scopes and kernels a step of this family has."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness, spans, trace, weights
from benchmark.drivers import train
from benchmark.families import phi4flash as fam
from benchmark.kernels import tl_flash, tl_flash_diff, tl_flash_mla
from benchmark.tests import tiny

CELL = "phi4-mini-flash-l6.train_lm_s4096"
PHI4_TINY = {
    "family": "phi4flash", "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 6, "vocab_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_norm_eps": 1e-5, "sliding_window": 16,
    "published_num_hidden_layers": 32, "layers": [0, 1, 16, 17, 18, 19],
    "mamba": {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 4},
    # float32 compute: at these widths bf16 tells nothing (the Kimi
    # family's note); the plumbing is what this rehearses
    "train": dict(
        tiny.GPT2_TINY["train"], compute_dtype="float32", remat=True),
}
PHI4_MIX = {
    "driver": "train", "seq_len": 128, "batch_size": 4, "micro_batches": 1,
    "ids": "uniform", "trace_seconds": 1,
    "check": {"steps": 3, "rows_per_block": 1},
}
# the float32 program reads 2e-7 / 4e-6 / 3e-5 / 7e-5 on seeds 3-5 here,
# the fp8 control 2e-4 / 0.03 / 0.06 / 0.03 at the least, the half batch
# 1e-3 / 0.35 / 0.3 / 0.05
LIMITS = {
    "loss_gap": 2e-5, "global_norm_gap": 0.001, "grad_norm_gap": 0.005,
    "delta_norm_gap": 0.005,
}


def _run(seed, **kw):
    cell = tiny.cell(PHI4_TINY, PHI4_MIX, LIMITS, seed=seed, seconds=0.3, **kw)
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
    """``weights.make_leaf`` knows every leaf's name (no sixth was
    needed), no family code sets a bias from the load, and the
    reference's gradient of every leaf is not nought."""
    model = fam.build(PHI4_TINY)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    ref = train.reference_run(fam, PHI4_TINY, PHI4_MIX, 5, shapes, 1)
    assert all(np.isfinite(g) and g > 0 for g in ref["grad"].values())
    # a key's bias moves no softmax: the two self-attention layers' are
    # the leaves the check leaves out of the parameters' change
    med = float(np.median(list(ref["grad"].values())))
    assert {n for n, g in ref["grad"].items() if g < 1e-3 * med} == {
        "blocks/1/mixer/k/b", "blocks/3/mixer/k/b"}
    params = weights.make_tree(5, shapes)
    assert len(jax.tree.leaves(params)) == len(ref["grad"]) == 100
    assert not hasattr(fam, "router_scores")


@pytest.fixture(scope="module")
def cell_cfg():
    return json.loads(
        (harness.HERE / "configs" / "phi4-mini-flash-l6.json").read_text())


def test_counts_at_the_cell_size(cell_cfg):
    cfg = cell_cfg
    shapes = jax.eval_shape(fam.build(cfg).init, jax.random.key(0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert round(held / 1e6, 1) == 697.1  # 16 B each: 11.15 GB of state
    assert fam.layer_kinds(cfg) == {
        "mamba": 2, "window": 1, "full": 1, "gmu": 1, "cross": 1}
    # a token meets every weight but the convolutions, norms, biases,
    # A_log, D and the lambdas: 0.4 M of 697
    per_token = fam.matmul_params(cfg)
    assert 0 < held - per_token < 0.5e6
    # the scan as its definition: 7 a channel and state
    assert fam.attn_flops(cfg, 0) == 2 * 7.0 * 5120 * 16
    # one pair of heads and key: two maps, scores at 64 and values at 128
    per_key = 20 * 2 * 2.0 * (64 + 128)
    assert fam.attn_flops(cfg, 10) - fam.attn_flops(cfg, 0) == 3 * per_key * 10
    # past the window the band stops growing, the two full maps go on
    assert fam.attn_flops(cfg, 2000) - fam.attn_flops(cfg, 1000) == (
        2 * per_key * 1000)
    band = (512 * 513 / 2 + (4096 - 512) * 512) / 4096  # mean of min(t+1, 512)
    assert fam.attn_flops(cfg, 2048.5, 4096) == pytest.approx(
        per_key * (band + 2 * 2048.5) + 2 * 7.0 * 5120 * 16)
    flops = fam.train_flops_per_token(cfg, 4096)
    assert round(flops / 1e9, 2) == 4.39
    # attention at 4,096: 4.6 % of the forward; the recurrence 0.08 %
    assert 0.04 < 3 * per_key * (band + 4097) / flops < 0.05


def test_the_cell_is_whole(cell_cfg):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert (harness.ROOT / conf["file"]).name == "phi4-mini-flash-l6.json"
    assert sorted(conf["reduced"]) == sorted(cell_cfg["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert cell_cfg["num_hidden_layers"] == len(cell_cfg["layers"])
    assert conf["source"] == cell_cfg["source"]
    mix = json.loads(
        (harness.HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (mix["seq_len"], mix["batch_size"], mix["micro_batches"]) == (
        4096, 4, 1)
    limits = json.loads(
        (harness.HERE / "limits" / f"{CELL}.json").read_text())
    assert set(limits["limits"]) == set(LIMITS)
    mine = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())
    }
    assert mine == {
        "train_step_device_ms", "attn_device_ms.train", "mlp_device_ms.train",
        "head_loss_device_ms.train", "update_device_ms.train",
        "scoped_device_pct.train", "idle_in_program_pct.train",
        "mamba_device_ms.train", "mamba_scan_device_ms.train",
        "gmu_device_ms.train", "diff_flash_roofline",
    }
    for m in bench["per_layer"]:
        if m["name"] in mine:
            assert m["moves"] == "train_tok_per_s"
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "train_tok_per_s")
    assert CELL in e2e["workloads"]


def test_the_catalog_keys_stand_as_published(cell_cfg):
    """Every number of the published config under its own key; the two
    that are cut are the two ``reduced`` names."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064,
    }
    differs = {k for k, v in published.items() if cell_cfg[k] != v}
    assert differs == set(cell_cfg["reduced"])
    assert cell_cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cell_cfg["published_num_hidden_layers"] == 32
    assert set(cell_cfg["assumed"]) >= {"mamba", "memory_and_kv", "weights"}


# --------------------------------------------- readers, on a synthetic trace
# One step of the tiny cut as the device's trace would hold it: a launch
# of jit_tl_train_step, instructions with the op paths the scopes give
# (forward, the block's recompute, backward), the flash kernels by name.
READERS = {
    "mamba_device_ms.train": 2 * (3 + 5 + 7) + 2 * (11 + 13),
    "mamba_scan_device_ms.train": 2 * (11 + 13),
    "gmu_device_ms.train": 17 + 19,
    "attn_device_ms.train": 3 * (23 + 29),
    "mlp_device_ms.train": 6 * 31,
}
STEP = "jit(tl_train_step)/"


def _synthetic(steps=2):
    ops, modules, t = [], [], 1000
    for _ in range(steps):
        start = t

        def op(name, path, us):
            nonlocal t
            ops.append(spans.Op(name, t, us * 1000, us * 1000, path=path))
            t += us * 1000 + 50

        for layer in range(2):  # two Mamba layers
            op("%fusion.1", STEP + "jvp(tl.mamba)/dot_general", 3)
            op("%while.1", STEP + "jvp(tl.mamba)/tl.mamba.scan/while", 11)
            op("%fusion.2", STEP + "transpose(jvp())/checkpoint/tl.mamba/mul", 5)
            op("%while.2", STEP + "transpose(jvp())/checkpoint/tl.mamba/"
               "tl.mamba.scan/while", 13)
            op("%fusion.3", STEP + "transpose(jvp())/checkpoint/"
               "transpose(jvp(tl.mamba))/dot_general", 7)
        op("%fusion.4", STEP + "jvp(tl.gmu)/dot_general", 17)
        op("%fusion.5", STEP + "transpose(jvp())/checkpoint/"
           "transpose(jvp(tl.gmu))/dot_general", 19)
        for layer in range(3):  # window, full, cross: two calls each
            for call in range(2):
                op("%jvp_tl_flash_fwd_.1 = custom-call",
                   STEP + "jvp(tl.attn)/tl_flash_fwd/pallas_call", 4)
                op("%checkpoint_jvp_tl_flash_fwd_.2 = custom-call",
                   STEP + "transpose(jvp())/checkpoint/tl.attn/tl_flash_fwd", 4)
                op("%transpose_jvp_tl_flash_bwd_dq__.3 = custom-call",
                   STEP + "transpose(jvp())/checkpoint/"
                   "transpose(jvp(tl.attn))/tl_flash_bwd_dq", 6)
                op("%transpose_jvp_tl_flash_bwd_dkv__.4 = custom-call",
                   STEP + "transpose(jvp())/checkpoint/"
                   "transpose(jvp(tl.attn))/tl_flash_bwd_dkv", 8)
            op("%fusion.6", STEP + "jvp(tl.attn)/dot_general", 23 - 8)
            op("%fusion.7", STEP + "transpose(jvp())/checkpoint/"
               "transpose(jvp(tl.attn))/dot_general", 29 - 36)
        for layer in range(6):
            op("%fusion.8", STEP + "jvp(tl.mlp)/dot_general", 31)
        op("%copy-done.1", "", 2)
        modules.append(trace.Event("jit_tl_train_step(123)", start, t - start))
        t += 5000
    return ops, modules


@pytest.fixture(scope="module")
def synthetic(cell_cfg):
    ops, modules = _synthetic()
    mix = json.loads(
        (harness.HERE / "traffic" / "train_lm_s4096.json").read_text())
    return {
        "trace": trace.Reduced(
            ops=[trace.Event(o.name, o.start, o.dur, o.self_ns) for o in ops],
            modules=modules, spans=[], busy_ns=0.0, chips=1),
        "tl_scoped": spans.Scoped(
            ops=ops, modules=modules, spans=[], window=None),
        "config": cell_cfg, "mix": mix,
        "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
    }


def _read(name, run):
    import benchmark.run as runner

    return runner.load_reader(name).read(run)


@pytest.mark.parametrize("name", sorted(READERS))
def test_scope_reader_on_the_synthetic_step(synthetic, name):
    assert _read(name, synthetic) == pytest.approx(READERS[name] / 1e3)


def test_the_groups_add_up_to_the_step(synthetic):
    split = spans.step_split(synthetic)
    parts = sum(_read(n, synthetic) for n in (
        "mamba_device_ms.train", "gmu_device_ms.train", "attn_device_ms.train",
        "mlp_device_ms.train"))
    assert parts + 0.002 == pytest.approx(split["total"] / 1e6)
    # what spans.GROUPS calls "other" is exactly the new scopes
    assert split["other"] / 1e6 == pytest.approx(
        _read("mamba_device_ms.train", synthetic)
        + _read("gmu_device_ms.train", synthetic))
    assert _read("scoped_device_pct.train", synthetic) == pytest.approx(
        100 * parts / (parts + 0.002))


def test_diff_flash_roofline_on_the_synthetic_step(synthetic, cell_cfg):
    from benchmark import roofline

    value = _read("diff_flash_roofline", synthetic)
    assert value > 0  # the synthetic calls last microseconds: no share
    tr = synthetic["trace"]
    assert len(tr.kernel_events("tl_flash_bwd_dkv")) == 2 * 6
    # a rematerialised block runs the forward twice a step
    assert len(tr.kernel_events("tl_flash_fwd")) == 2 * 2 * 6
    least = sum(
        2 * n * roofline.least_seconds(
            *tl_flash_diff.work(
                k, 4, 20, 4096, 64, 128, window=w, kv_heads=10),
            synthetic["peaks"])[0]
        for k in tl_flash_diff.MATMULS for w, n in ((512, 1), (None, 2))
    )
    spent = 2 * 6 * (4 + 4 + 6 + 8) / 1e6
    assert value == pytest.approx(100 * 2 * least / spent)
    # the band of 512 needs 0.23 of what a full causal map needs
    full = tl_flash_diff.work("tl_flash_fwd", 4, 20, 4096, 64, 128)[0]
    band = tl_flash_diff.work("tl_flash_fwd", 4, 20, 4096, 64, 128, window=512)[0]
    assert band / full == pytest.approx(0.2344, abs=1e-4)


def test_a_missing_kernel_is_none_and_never_nought(synthetic):
    run = dict(synthetic)
    run["trace"] = trace.Reduced(
        ops=[e for e in synthetic["trace"].ops if "bwd_dq" not in e.name],
        modules=synthetic["trace"].modules, spans=[], busy_ns=0.0, chips=1)
    assert _read("diff_flash_roofline", run) is None


def test_new_readers_find_nothing_in_an_older_program(tmp_path):
    """GPT-2's recording has none of the scopes and its configuration
    none of this family's keys: every new reader returns None (the line
    leaves the metric out), never 0: what the parent commit gives."""
    from benchmark.tests.test_trace import _unpack

    path = _unpack("train_2l_scoped.xplane.pb", tmp_path)
    cfg = json.loads((harness.HERE / "configs" / "gpt2-medium.json").read_text())
    run = {
        "tracedir": path, "trace": trace.reduce(path), "config": cfg,
        "mix": {"batch_size": 4, "micro_batches": 2, "seq_len": 1024},
        "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
    }
    for name in ("mamba_device_ms.train", "mamba_scan_device_ms.train",
                 "gmu_device_ms.train", "diff_flash_roofline"):
        assert _read(name, run) is None
    assert _read("mlp_device_ms.train", run) > 0  # the trace is read


def test_flash_diff_counts():
    # at equal widths and head counts, no window: tl_flash's own count
    for k in tl_flash.MATMULS:
        assert tl_flash_diff.work(k, 4, 16, 1024, 64, 64) == tl_flash.work(
            k, 4, 16, 1024, 64)
        assert tl_flash_diff.work(k, 4, 32, 4096, 192, 128) == (
            tl_flash_mla.work(k, 4, 32, 4096, 192, 128))
    assert tl_flash_diff.pairs(4096) == 4096 * 4096 / 2
    assert tl_flash_diff.pairs(4096, 512) == 512 * 513 / 2 + 3584 * 512
    assert tl_flash_diff.pairs(100, 512) == 100 * 101 / 2  # never binds
    f, b = tl_flash_diff.work(
        "tl_flash_fwd", 4, 20, 4096, 64, 128, window=512, kv_heads=10)
    assert f == 2 * (64 + 128) * 4 * 20 * tl_flash_diff.pairs(4096, 512)
    assert b == 4 * 4096 * 2 * (20 * (64 + 128) + 10 * (64 + 128))
    f, b = tl_flash_diff.work(
        "tl_flash_bwd_dkv", 4, 20, 4096, 64, 128, kv_heads=10)
    assert f == 2 * (2 * 64 + 2 * 128) * 4 * 20 * 4096 * 4096 / 2
    assert b == 4 * 4096 * 2 * (20 * (64 + 128) + 10 * 2 * (64 + 128))
