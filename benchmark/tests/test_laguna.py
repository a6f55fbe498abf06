"""The Laguna family through the benchmark's own driver at a tiny size on
the CPU: the program against ``reference/laguna.py``, the fp8 control
and the half-batch fault against the same limits, the counts of the
family file, the cell's files, and the two new readers (with the scope
readers the cell shares) on a synthetic trace of the scopes and kernels
a step of this family has."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness, spans, trace, weights
from benchmark.drivers import train
from benchmark.families import laguna as fam
from benchmark.kernels import tl_flash, tl_flash_diff
from benchmark.tests import tiny

CELL = "laguna-s2.1-l5e8.train_lm_s4096"
CELL_CFG = json.loads(
    (harness.HERE / "configs" / "laguna-s2.1-l5e8.json").read_text())
LAGUNA_TINY = {
    **{k: v for k, v in CELL_CFG.items()
       if k not in ("reduced", "assumed", "deployment")},
    "hidden_size": 32, "intermediate_size": 64, "head_dim": 8,
    "num_key_value_heads": 2, "num_attention_heads_per_layer": [12, 18, 18, 18, 12],
    "vocab_size": 128, "sliding_window": 16, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts": 4,
    "num_experts_per_tok": 4,
    "deployment_share": {"router_width": 16, "first_expert": 4},
    # float32 compute: at these widths bf16 tells nothing (the Kimi
    # family's note); the plumbing is what this rehearses
    "train": dict(
        tiny.GPT2_TINY["train"], compute_dtype="float32", remat=True),
}
LAGUNA_MIX = {
    "driver": "train", "seq_len": 128, "batch_size": 4, "micro_batches": 1,
    "ids": "uniform", "trace_seconds": 1,
    "check": {"steps": 3, "rows_per_block": 1},
}
# the float32 program reads 9.0e-8 / 4.4e-7 / 1.3e-6 / 1.2e-5 at the
# most on seeds 3-5 here, the fp8 control 5.7e-3 / 0.10 / 0.15 / 0.05,
# the half batch 1.1e-2 / 0.50 / 0.30 / 0.06
LIMITS = {
    "loss_gap": 2e-5, "global_norm_gap": 0.001, "grad_norm_gap": 0.005,
    "delta_norm_gap": 0.005,
}


def _run(seed, **kw):
    cell = tiny.cell(LAGUNA_TINY, LAGUNA_MIX, LIMITS, seed=seed, seconds=0.3, **kw)
    return cell, train.run(cell)


def test_train_runs_and_is_correct():
    _, res = _run(3)
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
    """``weights.make_leaf`` knows every leaf's name (the gate is a
    ``w``), and the reference's gradient of every leaf but the four
    selection biases (which choose and get none) is not nought."""
    model = fam.build(LAGUNA_TINY)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    ref = train.reference_run(fam, LAGUNA_TINY, LAGUNA_MIX, 5, shapes, 1)
    dead = {n for n, g in ref["grad"].items() if not g > 0}
    assert dead == {f"blocks/{i}/mlp/router/bias" for i in range(1, 5)}
    assert all(np.isfinite(g) for g in ref["grad"].values())
    params = weights.make_tree(5, shapes)
    assert len(jax.tree.leaves(params)) == len(ref["grad"]) == 73
    assert {n.rsplit("/", 2)[-2] for n in ref["grad"]} >= {"g", "router"}


def test_counts_at_the_cell_size():
    cfg = CELL_CFG
    shapes = jax.eval_shape(fam.build(cfg).init, jax.random.key(0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert round(held / 1e6, 1) == 811.0  # 16 B each: 12.98 GB of state
    # a token meets every projection, the gates, the head, the shared
    # expert and 10 x 8/256 of the held experts
    expert = 3 * 3072 * 1024
    assert round(fam.matmul_params(cfg) / 1e6, 1) == 482.3
    assert held - fam.matmul_params(cfg) == pytest.approx(
        4 * (8 - 10 * 8 / 256) * expert + 12544 * 3072 + 4 * 256 + 11 * 3072)
    # scores and values at 128 over the keys a head sees
    per_key = 4.0 * 128 * (2 * 48 + 3 * 72)
    assert fam.attn_flops(cfg, 10) == pytest.approx(per_key * 10)
    assert fam.attn_flops(cfg, 2000) - fam.attn_flops(cfg, 1000) == (
        pytest.approx(4.0 * 128 * 2 * 48 * 1000))  # the band stops growing
    band = (512 * 513 / 2 + (4096 - 512) * 512) / 4096
    assert fam.attn_flops(cfg, 2048.5, 4096) == pytest.approx(
        4.0 * 128 * (3 * 72 * band + 2 * 48 * 2048.5))
    flops = fam.train_flops_per_token(cfg, 4096)
    assert round(flops / 1e9, 3) == 3.355
    # attention at 4,096: 14 % of the work; the window layers' bands 5 %
    assert 0.13 < 3 * fam.attn_flops(cfg, 2048.5, 4096) / flops < 0.14
    assert fam.routed_experts(cfg) == {"k": 10, "held": (0, 8)}


def test_the_cell_is_whole():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert (harness.ROOT / conf["file"]).name == "laguna-s2.1-l5e8.json"
    assert sorted(conf["reduced"]) == sorted(CELL_CFG["reduced"]) == sorted([
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "num_attention_heads_per_layer", "mlp_layer_types", "gating_types"])
    assert conf["source"] == CELL_CFG["source"]
    assert CELL_CFG["num_hidden_layers"] == len(CELL_CFG["layer_types"]) == 5
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
        "moe_device_ms.train", "moe_experts_device_ms.train",
        "swa_device_ms.train", "swa_flash_roofline",
    }
    for m in bench["per_layer"]:
        if m["name"] in mine:
            assert m["moves"] == "train_tok_per_s"
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "train_tok_per_s")
    assert CELL in e2e["workloads"]


def test_the_catalog_keys_stand_as_published():
    """Every key of the catalog's published config, under its own key;
    those that differ are the ``reduced`` ones, and the lists among them
    are the published lists' first five entries."""
    row = {
        "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
        "num_hidden_layers": 48, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
        "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
        "layer_types": ["full_attention"] + ["sliding_attention"] * 3 + [
            "full_attention"] + ["sliding_attention"] * 3,
        "num_attention_heads_per_layer": [48, 72, 72, 72, 48, 72, 72, 72],
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "gating_types": ["per_head"] * 8,
    }
    differs = {k for k, v in row.items() if CELL_CFG[k] != v}
    assert differs == set(CELL_CFG["reduced"])
    for k in ("layer_types", "num_attention_heads_per_layer",
              "mlp_layer_types", "gating_types"):
        assert CELL_CFG[k] == row[k][:5]
    assert CELL_CFG["vocab_size"] * 8 == row["vocab_size"]
    yarn = CELL_CFG["rope_parameters"]["full_attention"]
    assert (yarn["factor"], yarn["partial_rotary_factor"]) == (128, 0.5)
    assert set(CELL_CFG["assumed"]) >= {"gate", "selection_bias", "hidden_act"}


# --------------------------------------------- readers, on a synthetic trace
# One step of the cut as the device's trace would hold it: a launch of
# jit_tl_train_step, instructions with the op paths the scopes give
# (forward, the block's recompute, backward), the flash kernels by name.
READERS = {
    "swa_device_ms.train": 3 * (2 * 4 + 6 + 8 + 23 + 29),
    "attn_device_ms.train": 2 * (2 * 4 + 6 + 8 + 23 + 29),
    "mlp_device_ms.train": 31,
    "moe_device_ms.train": 4 * (37 + 43 + 41),
    "moe_experts_device_ms.train": 4 * (43 + 41),
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

        for scope in ("tl.attn", "tl.swa", "tl.swa", "tl.swa", "tl.attn"):
            op("%jvp_tl_flash_fwd_.1 = custom-call",
               STEP + f"jvp({scope})/tl_flash_fwd/pallas_call", 4)
            op("%checkpoint_jvp_tl_flash_fwd_.2 = custom-call",
               STEP + f"transpose(jvp())/checkpoint/{scope}/tl_flash_fwd", 4)
            op("%transpose_jvp_tl_flash_bwd_dq__.3 = custom-call",
               STEP + f"transpose(jvp())/checkpoint/transpose(jvp({scope}))/"
               "tl_flash_bwd_dq", 6)
            op("%transpose_jvp_tl_flash_bwd_dkv__.4 = custom-call",
               STEP + f"transpose(jvp())/checkpoint/transpose(jvp({scope}))/"
               "tl_flash_bwd_dkv", 8)
            op("%fusion.6", STEP + f"jvp({scope})/dot_general", 23)
            op("%fusion.7", STEP + "transpose(jvp())/checkpoint/"
               f"transpose(jvp({scope}))/dot_general", 29)
        op("%fusion.8", STEP + "jvp(tl.mlp)/dot_general", 31)
        for layer in range(4):
            op("%fusion.9", STEP + "jvp(tl.moe)/tl.moe.route/top_k", 37)
            op("%gather.1", STEP + "jvp(tl.moe)/tl.moe.experts/gather", 43)
            op("%ragged-dot-none.2", "", 41)
        op("%copy-done.1", "", 2)
        modules.append(trace.Event("jit_tl_train_step(123)", start, t - start))
        t += 5000
    return ops, modules


@pytest.fixture(scope="module")
def synthetic():
    ops, modules = _synthetic()
    mix = json.loads(
        (harness.HERE / "traffic" / "train_lm_s4096.json").read_text())
    return {
        "trace": trace.Reduced(
            ops=[trace.Event(o.name, o.start, o.dur, o.self_ns) for o in ops],
            modules=modules, spans=[], busy_ns=0.0, chips=1),
        "tl_scoped": spans.Scoped(
            ops=ops, modules=modules, spans=[], window=None),
        "config": CELL_CFG, "mix": mix,
        "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
    }


def _read(name, run):
    import benchmark.run as runner

    return runner.load_reader(name).read(run)


@pytest.mark.parametrize("name", sorted(READERS))
def test_scope_reader_on_the_synthetic_step(synthetic, name):
    assert _read(name, synthetic) == pytest.approx(READERS[name] / 1e3)


def test_window_and_full_halves_add_up_with_the_rest(synthetic):
    split = spans.step_split(synthetic)
    # what spans.GROUPS calls "other" is tl.swa and tl.moe
    assert split["other"] / 1e6 == pytest.approx(
        _read("swa_device_ms.train", synthetic) + 4 * (37 + 43) / 1e3)
    assert split["unscoped"] / 1e6 == pytest.approx((4 * 41 + 2) / 1e3)


def test_swa_flash_roofline_on_the_synthetic_step(synthetic):
    from benchmark import roofline

    value = _read("swa_flash_roofline", synthetic)
    assert value > 0  # the synthetic calls last microseconds: no share
    tr = synthetic["trace"]
    assert len(tr.kernel_events("tl_flash_bwd_dkv")) == 2 * 5
    least = sum(
        roofline.least_seconds(
            *tl_flash_diff.work(k, 4, h, 4096, 128, 128, window=w, kv_heads=8),
            synthetic["peaks"])[0]
        for k in tl_flash_diff.MATMULS
        for h, w in ((48, None), (72, 512), (72, 512), (72, 512), (48, None))
    )
    spent = 2 * 5 * (4 + 4 + 6 + 8) / 1e6
    assert value == pytest.approx(100 * 2 * least / spent)


def test_a_missing_kernel_is_none_and_never_nought(synthetic):
    run = dict(synthetic)
    run["trace"] = trace.Reduced(
        ops=[e for e in synthetic["trace"].ops if "bwd_dq" not in e.name],
        modules=synthetic["trace"].modules, spans=[], busy_ns=0.0, chips=1)
    assert _read("swa_flash_roofline", run) is None


def test_new_readers_find_nothing_in_an_older_program(tmp_path):
    """GPT-2's recording has no ``tl.swa`` and its configuration none of
    this family's keys: both new readers return None (the line leaves
    the metric out), never 0: what the parent commit gives."""
    from benchmark.tests.test_trace import _unpack

    path = _unpack("train_2l_scoped.xplane.pb", tmp_path)
    cfg = json.loads((harness.HERE / "configs" / "gpt2-medium.json").read_text())
    run = {
        "tracedir": path, "trace": trace.reduce(path), "config": cfg,
        "mix": {"batch_size": 4, "micro_batches": 2, "seq_len": 1024},
        "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
    }
    for name in ("swa_device_ms.train", "swa_flash_roofline"):
        assert _read(name, run) is None
    assert _read("attn_device_ms.train", run) > 0  # the trace is read


def test_flash_swa_counts():
    """The window layers' count: ``tl_flash_diff``'s at d_v = d_qk."""
    for k in tl_flash.MATMULS:
        # one head width, no window, as many key heads: tl_flash's count
        assert tl_flash_diff.work(k, 4, 16, 1024, 128, 128) == tl_flash.work(
            k, 4, 16, 1024, 128)
    f, b = tl_flash_diff.work(
        "tl_flash_fwd", 4, 72, 4096, 128, 128, window=512, kv_heads=8)
    assert f == 2 * 2 * 128 * 4 * 72 * (512 * 513 / 2 + 3584 * 512)
    assert b == 4 * 4096 * 2 * (72 * 2 * 128 + 8 * 2 * 128)
    # a band of 512 needs 0.23 of what the whole triangle needs
    full = tl_flash_diff.work("tl_flash_bwd_dkv", 4, 72, 4096, 128, 128)[0]
    band = tl_flash_diff.work("tl_flash_bwd_dkv", 4, 72, 4096, 128, 128, 512)[0]
    assert band / full == pytest.approx(0.2344, abs=1e-4)
