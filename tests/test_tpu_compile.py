"""The main path's Pallas kernels through the TPU compiler, without a chip.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached. Interpret-mode parity tests
cannot see what it refuses (a slice not aligned to the tiling, a block
that is no legal tile, more VMEM than a kernel may use), so every
kernel is compiled here at the widths it serves, and has to be IN the
compiled program as a ``tpu_custom_call``.

Only one process may load the TPU's library, so everything that touches
the topology lives in this one file, inside fixtures: nothing at import,
in a ``skipif`` or in ``parametrize`` arguments, and no child process.
Nothing runs, so nothing here is a result or a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache.compilation_cache import reset_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """``compile_for_chip(fn, *shapes) -> compiled text`` for one
    described v5e chip. The persistent cache is off around these
    compiles: an executable for an absent chip can be written to it but
    not read back."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()  # tlint: disable=TL503 the switch is read at cache init

    def compile_(fn, *shapes):
        args = [
            jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
        ]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    reset_cache()  # tlint: disable=TL503 as above, switching back


def _kernel_lines(text: str, name: str) -> list[str]:
    return [
        ln for ln in text.splitlines()
        if "tpu_custom_call" in ln and name in ln
    ]


# BERT-base at seq 512 with its key-padding mask; a 128-wide-head
# decoder at seq 2048, causal (1024-blocks: one below the diagonal, two
# on it in sub-tiles); GPT-2 medium's micro-batch at seq 1024, causal
# (one 1024-block, all sub-tiles)
FLASH_SHAPES = [
    pytest.param(8, 12, 512, 64, True, False, id="B8-H12-T512-D64-kvmask"),
    pytest.param(2, 32, 2048, 128, False, True, id="B2-H32-T2048-D128-causal"),
    pytest.param(4, 16, 1024, 64, False, True, id="B4-H16-T1024-D64-causal"),
]


@pytest.mark.parametrize("B,H,T,D,masked,causal", FLASH_SHAPES)
def test_flash_forward_compiles(compile_for_chip, B, H, T, D, masked, causal):
    from tensorlink_tpu.ops.flash import flash_block_for
    from tensorlink_tpu.ops.pallas.flash_attention import (
        flash_attention_fwd_lse,
    )

    blk = flash_block_for(T, B)

    def fwd(q, k, v, *mask):
        return flash_attention_fwd_lse(
            q, k, v, mask[0] if mask else None, causal=causal,
            block_q=blk, block_k=blk,
        )

    qkv = [((B, H, T, D), BF16)] * 3
    mask = [((B, T), jnp.float32)] if masked else []
    assert _kernel_lines(compile_for_chip(fwd, *qkv, *mask), "tl_flash_fwd")


@pytest.mark.parametrize("B,H,T,D,masked,causal", FLASH_SHAPES)
def test_flash_backward_compiles(compile_for_chip, B, H, T, D, masked, causal):
    from tensorlink_tpu.ops.flash import flash_block_for
    from tensorlink_tpu.ops.pallas.flash_attention import flash_attention_bwd

    blk = flash_block_for(T, B)

    def bwd(q, k, v, o, lse, do, *mask):
        return flash_attention_bwd(
            q, k, v, o, lse, do, mask[0] if mask else None, causal=causal,
            block_q=blk, block_k=blk,
        )

    t4 = ((B, H, T, D), BF16)
    mask = [((B, T), jnp.float32)] if masked else []
    text = compile_for_chip(
        bwd, t4, t4, t4, t4, ((B, H, T), jnp.float32), t4, *mask
    )
    assert _kernel_lines(text, "tl_flash_bwd_dq")
    assert _kernel_lines(text, "tl_flash_bwd_dkv")


def test_flash_with_a_narrower_v_compiles(compile_for_chip):
    """MLA without rotary at Kimi-Linear's widths and the benchmark
    cell's shape: q, k 128 + 64 wide, v 128, 4 x 4,096 tokens, causal.
    ``flash_block_for`` has to give blocks the dq kernel's VMEM holds."""
    from tensorlink_tpu.ops.flash import flash_block_for
    from tensorlink_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd,
        flash_attention_fwd_lse,
    )

    B, H, T, D, Dv = 4, 32, 4096, 192, 128
    blk = flash_block_for(T, B, D)
    assert blk == 512 and flash_block_for(T, B) == 1024
    wide, narrow = ((B, H, T, D), BF16), ((B, H, T, Dv), BF16)

    def fwd(q, k, v):
        return flash_attention_fwd_lse(
            q, k, v, causal=True, block_q=blk, block_k=blk)

    def bwd(q, k, v, o, lse, do):
        return flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, block_q=blk, block_k=blk)

    assert _kernel_lines(compile_for_chip(fwd, wide, wide, narrow), "tl_flash_fwd")
    text = compile_for_chip(
        bwd, wide, wide, narrow, narrow, ((B, H, T), jnp.float32), narrow
    )
    assert _kernel_lines(text, "tl_flash_bwd_dq")
    assert _kernel_lines(text, "tl_flash_bwd_dkv")


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window512"])
def test_flash_at_differential_attention_widths_compiles(compile_for_chip, window):
    """Phi-4-mini-flash's differential attention at the benchmark cell's
    shape: q, k 64 wide, v a pair's 128, 20 query heads on 10 key heads,
    4 x 4,096 tokens, causal, over every earlier key and over a band of
    512 (the kernels' restricted grid), at the blocks ``flash_block_for``
    gives."""
    from tensorlink_tpu.ops.flash import flash_block_for
    from tensorlink_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd,
        flash_attention_fwd_lse,
    )

    B, H, Hkv, T, D, Dv = 4, 20, 10, 4096, 64, 128
    blk = flash_block_for(T, B, D)
    q, k, v = ((B, H, T, D), BF16), ((B, Hkv, T, D), BF16), ((B, Hkv, T, Dv), BF16)
    o = ((B, H, T, Dv), BF16)

    def fwd(q, k, v):
        return flash_attention_fwd_lse(
            q, k, v, causal=True, block_q=blk, block_k=blk, window=window)

    def bwd(q, k, v, o, lse, do):
        return flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, block_q=blk, block_k=blk,
            window=window)

    assert _kernel_lines(compile_for_chip(fwd, q, k, v), "tl_flash_fwd")
    text = compile_for_chip(bwd, q, k, v, o, ((B, H, T), jnp.float32), o)
    assert _kernel_lines(text, "tl_flash_bwd_dq")
    assert _kernel_lines(text, "tl_flash_bwd_dkv")


def test_phi4_flash_cell_step_fits_the_chip(topo, compile_for_chip, monkeypatch):
    """The step of ``phi4-mini-flash-l6.train_lm_s4096`` as the benchmark
    builds it (697 M parameters, Adam, bf16 on float32 masters, 4 rows of
    4,096, block remat), compiled for one described v5e: arguments,
    temporaries and code by the compiler's count stay under the 15.75
    GiB it allows, and the program holds the flash kernels at 64 / 128:
    two calls a layer in three layers, forward again in each block's
    recompute. A count, not a chip run."""
    import json
    import pathlib

    from benchmark.families import phi4flash as fam
    from tensorlink_tpu.config import TrainConfig
    from tensorlink_tpu.ops import flash
    from tensorlink_tpu.train.trainer import Trainer, TrainState

    monkeypatch.setattr(flash, "on_tpu", lambda: True)
    root = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
    cfg = json.loads((root / "configs" / "phi4-mini-flash-l6.json").read_text())
    mix = json.loads((root / "traffic" / "train_lm_s4096.json").read_text())
    hp = cfg["train"]
    model = fam.build(cfg)
    trainer = Trainer(model, fam.train_loss, TrainConfig(
        batch_size=mix["batch_size"], micro_batches=mix["micro_batches"],
        learning_rate=hp["learning_rate"], optimizer=hp["optimizer"],
        weight_decay=hp["weight_decay"], schedule=hp["schedule"],
        warmup_steps=hp["warmup_steps"], grad_clip_norm=hp["clip_norm"],
        dtype=hp["compute_dtype"],
    ))
    one_chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree)

    shapes = jax.eval_shape(model.init, jax.random.key(0))
    state = jax.eval_shape(
        lambda p: TrainState.create(p, trainer.optimizer), shapes)
    ids = jax.ShapeDtypeStruct(
        (mix["batch_size"], mix["seq_len"]), jnp.int32, sharding=one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    # ``compile_for_chip`` is asked for its switch alone: the persistent
    # cache is off while this module's compiles run
    compiled = trainer._train_step.lower(
        described(state), {"input_ids": ids, "labels": ids}, described(key),
    ).compile()
    mem = compiled.memory_analysis()
    gib = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.generated_code_size_in_bytes
    ) / 2 ** 30
    assert 11.5 < gib < 15.75, gib  # 697.1 M x 12 B of arguments alone: 7.79
    text = compiled.as_text()
    wide = _kernel_lines(text, "tl_flash_fwd")
    assert len(wide) == 12 and all(
        "bf16[4,20,4096,64]" in ln and "bf16[4,10,4096,128]" in ln
        for ln in wide)
    assert len(_kernel_lines(text, "tl_flash_bwd_dq")) == 6
    assert len(_kernel_lines(text, "tl_flash_bwd_dkv")) == 6



@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("pools", ["bf16", "int8"])
def test_paged_decode_compiles(compile_for_chip, monkeypatch, pools, T):
    """Mistral-7B widths (32 query / 8 KV heads of 128), 16-token
    pages, 8 slots of a 1024-token view, window 4096: single-token
    decode and a verify-K chunk, bf16 and int8 pools."""
    from tensorlink_tpu.ops.pallas import paged_decode

    # the gate asks jax.devices(), which is the CPU here
    monkeypatch.setattr(paged_decode, "on_tpu", lambda: True)
    B, H, Hkv, D, bs, MB = 8, 32, 8, 128, 16, 64
    NB = B * MB + 1
    quant = pools == "int8"
    pool = ((NB, bs, Hkv, D), jnp.int8 if quant else BF16)
    scales = [((NB, bs, Hkv), jnp.float32)] * 2 if quant else []

    def decode(q, k, v, bt, lengths, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return paged_decode.paged_decode_attention(
            q, k, v, bt, lengths, window=4096, **kw
        )

    text = compile_for_chip(
        decode, ((B, T, H, D), BF16), pool, pool, ((B, MB), jnp.int32),
        ((B,), jnp.int32), *scales,
    )
    assert _kernel_lines(text, "tl_paged_decode")


def test_paged_decode_with_mask_compiles(compile_for_chip, monkeypatch):
    """The explicit view-width mask: its (T, bs) page block is legal
    only page-major."""
    from tensorlink_tpu.ops.pallas import paged_decode

    monkeypatch.setattr(paged_decode, "on_tpu", lambda: True)
    B, T, H, D, bs, MB = 8, 4, 16, 128, 16, 64  # an MHA shape
    NB = B * MB + 1
    pool = ((NB, bs, H, D), BF16)

    def decode(q, k, v, bt, lengths, mask):
        return paged_decode.paged_decode_attention(
            q, k, v, bt, lengths, mask=mask
        )

    text = compile_for_chip(
        decode, ((B, T, H, D), BF16), pool, pool, ((B, MB), jnp.int32),
        ((B,), jnp.int32), ((B, 1, T, MB * bs), jnp.bool_),
    )
    assert _kernel_lines(text, "tl_paged_decode")


@pytest.mark.parametrize(
    "kind,D,bias",
    [("layer", 768, True), ("rms", 4096, False)],
    ids=["layer-D768", "rms-D4096"],
)
def test_fused_residual_norm_compiles(
    compile_for_chip, monkeypatch, kind, D, bias
):
    from tensorlink_tpu.ops.pallas import decode_glue

    monkeypatch.setattr(decode_glue, "on_tpu", lambda: True)

    def glue(x, res, scale, *b):
        return decode_glue.fused_residual_norm(
            x, res, scale, b[0] if b else None, kind=kind
        )

    row = ((8, 1, D), BF16)
    vec = [((D,), jnp.float32)] * (2 if bias else 1)
    assert _kernel_lines(
        compile_for_chip(glue, row, row, *vec), "tl_decode_glue"
    )


def _kda_operands(B, T, H, mm, d=128):
    """q, k, v, g, beta of ``kda_chunked`` as (shape, dtype)."""
    return [((B, T, H, d), mm)] * 3 + [
        ((B, T, H, d), jnp.float32), ((B, T, H), jnp.float32)
    ]


@pytest.mark.parametrize("T,mm", [
    (4096, BF16), (4096, jnp.float32), (4000, BF16),
], ids=["T4096-bf16", "T4096-f32", "T4000-padded"])
def test_kda_forward_compiles(compile_for_chip, monkeypatch, T, mm):
    """``tl_kda_fwd`` at the Kimi cell's shape, 4 rows of 32 heads of
    128 (a VMEM overrun or a tile Mosaic refuses shows here, without
    the chip), and through ``kda_chunked``'s own padding."""
    from tensorlink_tpu.ops import kda

    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    text = compile_for_chip(kda.kda_chunked, *_kda_operands(4, T, 32, mm))
    assert _kernel_lines(text, "tl_kda_fwd")
    assert "triangular-solve" not in text and "TriangularSolve" not in text


def test_kda_gradient_holds_the_kernel_once(compile_for_chip, monkeypatch):
    from tensorlink_tpu.ops import kda

    monkeypatch.setattr(kda, "on_tpu", lambda: True)

    def grads(*xs):
        return jax.grad(
            lambda *a: jnp.sum(jnp.sin(kda.kda_chunked(*a))), range(5)
        )(*xs)

    text = compile_for_chip(grads, *_kda_operands(2, 256, 4, BF16))
    assert len(_kernel_lines(text, "tl_kda_fwd")) == 1


@pytest.mark.parametrize("where,reason", [
    ("a mesh", "partitioned by XLA"), ("heads of 64", "64 / 64 wide"),
])
def test_kda_gate_closes_with_a_reason(topo, monkeypatch, where, reason):
    """Where XLA partitions (a four-chip mesh it splits) and for a head
    width the kernel does not take, ``kda_chunked`` compiles as the XLA
    program and the gate says why."""
    from tensorlink_tpu.ops import kda
    from tensorlink_tpu.runtime.flight import default_recorder

    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    d = 64 if where == "heads of 64" else 128
    if where == "a mesh":
        mesh = Mesh([[dev] for dev in topo.devices], ("model", "data"))
    else:
        mesh = Mesh(topo.devices[:1], ("model",))
    args = [
        jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, P()))
        for shape, dt in _kda_operands(2, 128, 4, BF16, d)
    ]
    seen = len(default_recorder().events(kind="kernel.gate_closed"))
    with jax.set_mesh(mesh):
        text = jax.jit(kda.kda_chunked).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    new = default_recorder().events(kind="kernel.gate_closed")[seen:]
    assert new and new[-1]["attrs"]["kernel"] == "tl_kda_fwd"
    assert reason in new[-1]["attrs"]["reason"], new


def test_gate_closes_where_xla_partitions(topo, compile_for_chip, monkeypatch):
    """XLA cannot split a Mosaic kernel: on a four-chip mesh whose axes
    it partitions, lowering one raises. The gate has to close there —
    the program compiles without the kernel — and say why."""
    from tensorlink_tpu.ops.pallas import decode_glue
    from tensorlink_tpu.runtime.flight import default_recorder

    monkeypatch.setattr(decode_glue, "on_tpu", lambda: True)
    mesh = Mesh(
        [[d] for d in topo.devices], ("model", "data"),
    )
    rows = NamedSharding(mesh, P())
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=rows)
        for s, dt in [((8, 1, 768), BF16)] * 2 + [((768,), jnp.float32)]
    ]
    seen = len(default_recorder().events(kind="kernel.gate_closed"))
    with jax.set_mesh(mesh):
        text = jax.jit(
            lambda x, r, s: decode_glue.fused_residual_norm(
                x, r, s, kind="layer"
            )
        ).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    why = [
        e["attrs"]["reason"] for e in
        default_recorder().events(kind="kernel.gate_closed")[seen:]
    ]
    assert why and "partitioned by XLA" in why[-1], why


def test_expert_parallel_lowers_to_all_to_all(topo, compile_for_chip):
    """``MoEFeedForward`` under a four-way expert mesh, through the
    compiler that matters: with the mesh ambient its constraints come
    out as all-to-alls (dispatch and return) with no token all-gather
    and no combine all-reduce. ``tests/test_moe.py`` holds the same pin
    for the CPU partitioner and the numbers against one device."""
    import numpy as np

    from tensorlink_tpu.analysis.hlo import parse_hlo
    from tensorlink_tpu.nn.moe import MoEFeedForward

    mesh = Mesh(np.array(topo.devices), ("model",))
    moe = MoEFeedForward(dim=1024, hidden_dim=4096, num_experts=8, top_k=2)
    params = jax.tree.map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            leaf.shape, BF16, sharding=NamedSharding(mesh, spec)
        ),
        jax.eval_shape(moe.init, jax.random.key(0)), moe.param_spec("model"),
    )
    x = jax.ShapeDtypeStruct(
        (8, 512, 1024), BF16, sharding=NamedSharding(mesh, P())
    )
    with jax.set_mesh(mesh):
        ir = parse_hlo(jax.jit(moe.apply).lower(params, x).compile().as_text())
    assert ir.count("all-to-all") == 2
    assert ir.count("all-gather") == 0
    assert ir.count("all-reduce") == 0
