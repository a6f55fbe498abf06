#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the served and the trained main path once, through the entry
points a user calls, at the published widths of models the repo
supports, on ONE TPU chip in ONE process (the only one that touches
JAX; a chip belongs to one process at a time). Weights are random, made
from ``SEED``. Every phase prints one JSON object; the last line of
standard output is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it. Any phase whose check does not hold raises, so the
process exits non-zero with no such line. Without an accelerator it
stops at the device phase.

    python chip_smoke.py              # one chip: device, serve,
                                      # serve-kernel, train, kda, mamba, cache
    python chip_smoke.py --multichip  # four chips: device, multichip

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (runtime/compile_cache.py). The phase
functions take their model configuration as an argument:
``tests/test_chip_smoke.py`` runs them at tiny sizes on the CPU, where
no kernel can be in a program, so what only a chip can show (which
kernels the compiled programs contain) is checked in :func:`main`.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import importlib.metadata
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

from benchmark.harness import gate_reasons, kernels_in

SEED = 0
# bf16 keeps 8 bits of mantissa: one ulp is 2**-8 of the value. The
# kernels feed the MXU bf16 (flash rounds its probabilities to bf16
# before the PV product) and round their result to bf16; the
# references run in f32 at the highest matmul precision. Both errors
# scale with the values being averaged, not with the one output they
# land on, so a kernel is held to ULPS bf16 ulps of its reference's
# largest value. A wrong mask or position is off by whole values, and
# fp8 or int8 arithmetic by 16 times this.
ULPS = 2
# sharded against unsharded training in bf16 on ln(50257) = 10.8 losses
LOSS_BAND = 0.05
# Two bf16 programs for one model (cached decode against a plain
# forward pass, a kernel against XLA) round differently, and between
# the near-tied logits of random weights that can move an argmax: a
# greedy token is held to being among the reference's RANK_TOL best,
# out of a vocabulary of tens of thousands. A wrong cache, mask or
# position puts it at a random rank.
RANK_TOL = 5


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def finish(phase: str, facts: dict, failures: list[str]) -> dict:
    """Print the phase's line; a phase with failures ends the run."""
    line = {"phase": phase, "ok": not failures, **facts}
    if failures:
        line["failures"] = failures
    print(json.dumps(line, default=str), flush=True)
    if failures:
        raise SmokeFailure(f"{phase}: " + "; ".join(failures))
    return facts


def decode_kernels(sched) -> list[str]:
    """The kernels in a serving engine's compiled decode program (a
    second lower + compile of the program the engine runs: with the
    persistent cache on, a read)."""
    decode = next(p for p in sched.audit_programs() if p["name"] == "decode")
    return kernels_in(decode["lower"]().compile().as_text())


def greedy_consistency(model, params, prompts, outs) -> dict:
    """Hold emitted greedy tokens to the model itself: ONE plain
    forward pass (no cache, no engine, teacher-forced on prompt +
    emitted tokens), then for every emitted token its rank and logit
    margin under the logits that predict it."""
    import jax
    import jax.numpy as jnp

    n_prompt = len(prompts[0])
    ids = np.stack([np.concatenate([p, o]) for p, o in zip(prompts, outs)])

    @jax.jit
    def score(params, ids):
        logits = model.apply(params, ids[:, :-1]).astype(jnp.float32)
        pred = logits[:, n_prompt - 1:]  # predicts the emitted tokens
        chosen = jnp.take_along_axis(
            pred, ids[:, n_prompt:, None], axis=-1
        )
        rank = jnp.sum(pred > chosen, axis=-1)
        return rank, jnp.max(pred, axis=-1) - chosen[..., 0]

    rank, margin = (np.asarray(x) for x in score(params, jnp.asarray(ids)))
    return {
        "worst_rank": int(rank.max()),
        "worst_margin": round(float(margin.max()), 4),
        "argmax_share": round(float((rank == 0).mean()), 4),
    }


def _version(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def _placement(mesh, tree, names: dict[str, tuple]) -> dict:
    """Over the devices of ``mesh``: parameter bytes and memory in use
    on each, and the device set of a few named parameters' shards."""
    import jax

    def shards_of(path):
        arr = tree
        for k in path:
            arr = arr[k]
        shards = arr.addressable_shards
        return {
            "shape": list(arr.shape),
            "shard_shape": list(shards[0].data.shape),
            "devices": sorted(s.device.id for s in shards),
        }

    devices = list(mesh.devices.flat)
    param_bytes = {d.id: 0 for d in devices}
    for arr in jax.tree.leaves(tree):
        for sh in arr.addressable_shards:
            param_bytes[sh.device.id] += sh.data.nbytes
    return {
        "param_bytes": param_bytes,
        "bytes_in_use": {
            d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in devices
        },
        "shards": {name: shards_of(path) for name, path in names.items()},
    }


def _spread_failures(what: str, param_bytes: dict[int, int]) -> list[str]:
    empty = [d for d, n in param_bytes.items() if n == 0]
    return [f"{what}: devices {empty} hold no parameter bytes"] if empty else []


# ------------------------------------------------------------------ device
def device_phase(want_count: int) -> dict:
    """Refuse anything but ``want_count`` TPU chips, rebuild the native
    wire codec from the committed source, and turn the compile cache
    on before the first compile. Returns the phase's facts: the device
    as JAX reports it, the cache directory and its entry count."""
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu" or device["count"] != want_count:
        finish("device", device, [
            f"need {want_count} tpu device(s), jax reports "
            f"{device['count']} x {device['platform']}"
        ])

    from tensorlink_tpu import native
    from tensorlink_tpu.runtime.compile_cache import (
        cache_entries,
        enable_compile_cache,
    )

    # the .so is git-ignored but the chip tool copies the tree as it
    # stands: what runs here must be built from what git would commit,
    # and a missing toolchain must show instead of the Python fallback
    Path(native.__file__).with_name("libwirecodec.so").unlink(missing_ok=True)
    have_native = native.have_native()
    cache_dir = enable_compile_cache()
    facts = {
        **device,
        "jax": jax.__version__,
        "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": cache_entries(cache_dir),
        "native_wire_codec": have_native,
    }
    failures = []
    if not have_native:
        failures.append("libwirecodec.so did not build from wirecodec.cpp")
    if cache_dir is None:
        failures.append("the compile cache directory could not be made")
    return finish("device", facts, failures)


# ------------------------------------------------------------------- serve
def _prompts(vocab: int, n: int, shared: int, unique: int) -> list:
    r = np.random.default_rng(SEED)
    system = r.integers(0, vocab, (shared,))
    return [
        np.concatenate([system, r.integers(0, vocab, (unique,))])
        .astype(np.int32)
        for _ in range(n)
    ]


async def _serve_over_sockets(engine, prompts, **engine_kw):
    """README "Serving" wiring: validator + one worker with a paged
    engine + a user whose remote client submits over localhost."""
    from tensorlink_tpu.config import NodeConfig
    from tensorlink_tpu.roles.user import UserNode
    from tensorlink_tpu.roles.validator import ValidatorNode
    from tensorlink_tpu.roles.worker import WorkerNode

    val = ValidatorNode(NodeConfig(role="validator"))
    worker = WorkerNode(NodeConfig(role="worker"))
    user = UserNode(NodeConfig(role="user"))
    nodes = [val, worker, user]
    for n in nodes:
        await n.start()
    try:
        sched = worker.serving_engine(engine, paged=True, **engine_kw)
        await val.ping(await val.connect("127.0.0.1", worker.port))
        client = user.remote_serving(
            await user.connect("127.0.0.1", val.port)
        )
        t0 = time.perf_counter()
        # the first request alone, so that its system-prompt blocks are
        # in the prefix index when the others arrive together
        outs = [np.asarray(
            await client.result(await client.submit(prompts[0]))
        )]
        rids = [await client.submit(p) for p in prompts[1:]]
        outs += [np.asarray(await client.result(rid)) for rid in rids]
        wall = time.perf_counter() - t0
        compiles = worker.flight.events(kind="serving.compile")
    finally:
        for n in nodes:
            await n.stop()
    return sched, outs, wall, compiles


def serve_phase(
    cfg, *, max_len: int, requests: int = 8, shared: int = 64,
    unique: int = 32, new_tokens: int = 32, block_size: int = 16,
) -> tuple[dict, list[str]]:
    """The normal served path on GPT-2 ``cfg`` in bf16: ``requests``
    greedy requests sharing a ``shared``-token system prompt, through
    UserNode -> ValidatorNode -> WorkerNode's paged engine over real
    sockets. Every served token is held to the model's own logits
    (:func:`greedy_consistency`); how many requests equal
    ``InferenceEngine.generate``'s tokens outright is reported."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.config import MeshConfig
    from tensorlink_tpu.models.gpt2 import GPT2
    from tensorlink_tpu.parallel.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from tensorlink_tpu.runtime.mesh import make_mesh

    model = GPT2(cfg)
    engine = InferenceEngine(
        make_mesh(MeshConfig()), model, model.init(jax.random.key(SEED)),
        max_len=max_len, cache_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    gen = GenerationConfig(max_new_tokens=new_tokens)
    prompts = _prompts(cfg.vocab_size, requests, shared, unique)
    refs = engine.generate(np.stack(prompts), gen)

    sched, outs, wall, compiles = asyncio.run(_serve_over_sockets(
        engine, prompts, slots=requests, gen=gen, block_size=block_size,
        warm_buckets=True,
    ))
    served = greedy_consistency(model, engine.params, prompts, outs)
    facts = {
        "model": f"GPT-2 {cfg.num_layers}L dim {cfg.dim} "
                 f"heads {cfg.num_heads} vocab {cfg.vocab_size}",
        "dtype": "bfloat16",
        "max_len": max_len,
        "requests": requests,
        "prompt_tokens": shared + unique,
        "tokens": int(sum(len(o) for o in outs)),
        "compile_s": round(
            sum(e["attrs"]["compile_s"] for e in compiles), 3
        ),
        "compile_cache_hits": [
            e["attrs"].get("compile_cache_hit") for e in compiles
        ],
        "wall_s": round(wall, 3),
        "prefix_hit_rate": round(sched.prefix_hit_rate(), 4),
        "identical_to_generate": sum(
            np.array_equal(out, ref) for out, ref in zip(outs, refs)
        ),
        "teacher_forced": served,
        "teacher_forced_generate": greedy_consistency(
            model, engine.params, prompts, list(refs)
        ),
        "rank_tol": RANK_TOL,
        "decode_kernels": decode_kernels(sched),
        "gates_closed": gate_reasons(),
    }
    failures = []
    if len(outs) != requests or any(len(o) != new_tokens for o in outs):
        failures.append("a request came back short")
    if served["worst_rank"] >= RANK_TOL:
        failures.append(
            f"a served token ranks {served['worst_rank']} under the "
            f"model's own logits (tolerance {RANK_TOL})"
        )
    return facts, failures


# ------------------------------------------------------------ serve-kernel
def _paged_case(B, T, H, Hkv, D, bs, MB, quant: bool):
    """Seeded pools as the engine lays them out: distinct pages per
    live block, the sentinel past each row's frontier."""
    import jax.numpy as jnp

    from tensorlink_tpu.ops.quant import quantize_kv_int8

    r = np.random.default_rng(SEED)
    NB = B * MB + 1
    q = jnp.asarray(r.standard_normal((B, T, H, D)), jnp.bfloat16)
    k = jnp.asarray(r.standard_normal((NB, bs, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(r.standard_normal((NB, bs, Hkv, D)), jnp.bfloat16)
    lives = r.integers(T, MB * bs + 1, (B,))
    lives[0] = MB * bs  # one full row
    perm = r.permutation(NB)
    bt = np.full((B, MB), NB, np.int32)
    nxt = 0
    for b, live in enumerate(lives):
        n = -(-int(live) // bs)
        bt[b, :n] = perm[nxt:nxt + n]
        nxt += n
    scales = {}
    if quant:
        k, ks = quantize_kv_int8(k)
        v, vs = quantize_kv_int8(v)
        scales = {"k_scale": ks, "v_scale": vs}
    return q, k, v, jnp.asarray(bt), jnp.asarray(lives, jnp.int32), scales


def _held_to(ref, fn, *args) -> dict:
    """Run ``fn(*args)`` as one compiled program and hold its output
    to ``ref``; ``kernels`` says which of the repo's kernels that
    program contains, so a gate that closed cannot pass for the kernel."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    return {
        "max_abs": round(float(np.abs(out - ref).max()), 5),
        "tolerance": round(ULPS * 2.0 ** -8 * float(np.abs(ref).max()), 5),
        "kernels": kernels_in(compiled.as_text()),
    }


def kernel_parity(cfg, *, max_len: int, block_size: int,
                  interpret: bool = False) -> tuple[dict, list[str]]:
    """Each kernel against its jnp reference on seeded inputs at
    ``cfg``'s widths. The references run at highest matmul precision:
    they are what the kernels are held to."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.nn.attention import dot_product_attention
    from tensorlink_tpu.ops.flash import flash_attention
    from tensorlink_tpu.ops.pallas.decode_glue import fused_residual_norm
    from tensorlink_tpu.ops.pallas.paged_decode import (
        paged_decode_attention,
        paged_decode_reference,
    )

    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    D = cfg.dim // H
    errs: dict[str, dict] = {}
    for quant in (False, True):
        for T in (1, 4):
            q, k, v, bt, lengths, scales = _paged_case(
                8, T, H, Hkv, D, block_size, max_len // block_size, quant
            )
            with jax.default_matmul_precision("highest"):
                ref = paged_decode_reference(
                    q, k, v, bt, lengths, window=cfg.attn_window, **scales
                )
            name = f"paged_decode_{'int8' if quant else 'bf16'}_T{T}"
            errs[name] = _held_to(
                ref,
                lambda q, k, v, bt, lengths, scales: paged_decode_attention(
                    q, k, v, bt, lengths, window=cfg.attn_window,
                    interpret=interpret, **scales,
                ),
                q, k, v, bt, lengths, scales,
            )

    r = np.random.default_rng(SEED)
    x, res = (
        jnp.asarray(r.standard_normal((8, 1, cfg.dim)), jnp.bfloat16)
        for _ in range(2)
    )
    scale = jnp.asarray(1 + 0.1 * r.standard_normal(cfg.dim), jnp.float32)
    rf = x.astype(jnp.float32) + res.astype(jnp.float32)
    yf = rf * jax.lax.rsqrt(
        jnp.mean(jnp.square(rf), -1, keepdims=True) + cfg.rms_eps
    ) * scale
    errs["fused_residual_norm_rms"] = _held_to(
        yf,
        lambda x, res, scale: fused_residual_norm(
            x, res, scale, kind="rms", eps=cfg.rms_eps, interpret=interpret
        )[1],
        x, res, scale,
    )

    T = min(512, max_len)
    fq = jnp.asarray(r.standard_normal((2, T, H, D)), jnp.bfloat16)
    fk, fv = (
        jnp.asarray(r.standard_normal((2, T, Hkv, D)), jnp.bfloat16)
        for _ in range(2)
    )
    with jax.default_matmul_precision("highest"):
        ref = dot_product_attention(fq, fk, fv, causal=True)
    errs["flash_fwd_causal"] = _held_to(
        ref,
        lambda q, k, v: flash_attention(q, k, v, None, True, interpret),
        fq, fk, fv,
    )

    facts = {"ulps_of_largest_value": ULPS, "kernel_errors": errs}
    failures = [
        f"{k} is {e['max_abs']} off its reference (tolerance "
        f"{e['tolerance']})"
        for k, e in errs.items() if not e["max_abs"] <= e["tolerance"]
    ]
    return facts, failures


def _run_paged(engine, prompts, gen, *, kv_quant, block_size, kernel: bool):
    """One paged engine, driven directly. ``kernel=False`` pins the XLA
    gather path with the kernel's own kill switch, which is read while
    the programs trace."""
    from tensorlink_tpu.parallel.serving import PagedContinuousBatchingEngine
    from tensorlink_tpu.runtime.flight import FlightRecorder

    prev = os.environ.get("TL_PAGED_KERNEL")
    if not kernel:
        os.environ["TL_PAGED_KERNEL"] = "0"
    try:
        rec = FlightRecorder()
        sched = PagedContinuousBatchingEngine(
            engine, slots=len(prompts), gen=gen, kv_quant=kv_quant,
            block_size=block_size, warm_buckets=True, recorder=rec,
        )
        t0 = time.perf_counter()
        rids = [sched.submit(p) for p in prompts]
        sched.run_until_idle()
        tokens = [np.asarray(sched.result(rid)) for rid in rids]
        wall = time.perf_counter() - t0
        kernels = decode_kernels(sched)
    finally:
        if prev is None:
            os.environ.pop("TL_PAGED_KERNEL", None)
        else:
            os.environ["TL_PAGED_KERNEL"] = prev
    compile_s = sum(
        e["attrs"]["compile_s"] for e in rec.events(kind="serving.compile")
    )
    return tokens, kernels, round(compile_s, 3), round(wall, 3)


def serve_kernel_phase(
    cfg, *, max_len: int, requests: int = 8, prompt_len: int = 96,
    new_tokens: int = 32, block_size: int = 16, interpret: bool = False,
) -> tuple[dict, list[str]]:
    """The paged engine driven directly on Llama-shaped ``cfg`` (the one
    supported family whose 128-wide heads open the paged-decode
    kernel's gate on hardware), bf16 weights, with bf16 and with int8
    KV pools; then each kernel against its reference."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.config import MeshConfig
    from tensorlink_tpu.models.llama import Llama
    from tensorlink_tpu.parallel.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from tensorlink_tpu.runtime.mesh import make_mesh

    model = Llama(cfg)
    engine = InferenceEngine(
        make_mesh(MeshConfig()), model, model.init(jax.random.key(SEED)),
        max_len=max_len, cache_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    gen = GenerationConfig(max_new_tokens=new_tokens)
    prompts = _prompts(cfg.vocab_size, requests, 0, prompt_len)
    facts: dict = {
        "model": f"Llama-shaped {cfg.num_layers}L dim {cfg.dim} "
                 f"heads {cfg.num_heads}/{cfg.num_kv_heads} hidden "
                 f"{cfg.hidden_dim} vocab {cfg.vocab_size} window "
                 f"{cfg.attn_window}",
        "dtype": "bfloat16", "max_len": max_len, "requests": requests,
    }
    failures: list[str] = []
    for quant in (None, "int8"):
        kw = dict(kv_quant=quant, block_size=block_size)
        toks, kernels, compile_s, wall = _run_paged(
            engine, prompts, gen, kernel=True, **kw
        )
        ref_toks, ref_kernels, _, _ = _run_paged(
            engine, prompts, gen, kernel=False, **kw
        )
        same = [
            int(np.argmin(np.append(a == b, False)))  # matching prefix
            for a, b in zip(toks, ref_toks)
        ]
        held = greedy_consistency(model, engine.params, prompts, toks)
        facts[f"kv_{quant or 'bf16'}"] = {
            "tokens": int(sum(len(t) for t in toks)),
            "compile_s": compile_s,
            "wall_s": wall,
            "decode_kernels": kernels,
            "reference_path_kernels": ref_kernels,
            "greedy_agreement": round(
                sum(same) / (new_tokens * requests), 4
            ),
            "requests_identical": sum(s == new_tokens for s in same),
            "teacher_forced": held,
            "teacher_forced_reference_path": greedy_consistency(
                model, engine.params, prompts, ref_toks
            ),
        }
        # int8 pools quantize what attention reads: a different result
        # by design, reported and not held to the bf16 model's ranking
        if quant is None and held["worst_rank"] >= RANK_TOL:
            failures.append(
                f"a token of the kernel path ranks {held['worst_rank']} "
                f"under the model's own logits (tolerance {RANK_TOL})"
            )
    parity, parity_failures = kernel_parity(
        cfg, max_len=max_len, block_size=block_size, interpret=interpret
    )
    facts.update(parity, rank_tol=RANK_TOL)
    failures += parity_failures
    facts["gates_closed"] = gate_reasons()
    return facts, failures


# ------------------------------------------------------------------- train
def train_phase(
    cfg, *, batch: int = 8, seq: int = 512, steps: int = 5,
    classes: int = 3, learning_rate: float = 2e-6,
) -> tuple[dict, list[str]]:
    """``Trainer`` on ``BertClassifier(cfg)``, bf16 compute, adam, on
    one fixed seeded batch with a key-padding mask. The learning rate
    is small because the weights are random and there is no warm-up: at
    fine-tuning's usual 2e-5 adam overshoots on this batch of 8 and the
    loss rises before it falls (BERT-base, on the chip at 1e-4 and on
    the CPU at 2e-5)."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.config import TrainConfig
    from tensorlink_tpu.models.bert import BertClassifier
    from tensorlink_tpu.train.trainer import Trainer, softmax_cross_entropy

    model = BertClassifier(cfg, num_classes=classes)

    def loss_fn(module, params, b, rng):
        logits = module.apply(
            params, b["input_ids"], attention_mask=b["attention_mask"]
        )
        return softmax_cross_entropy(logits, b["labels"])

    tr = Trainer(model, loss_fn, TrainConfig(
        batch_size=batch, micro_batches=1, learning_rate=learning_rate,
        optimizer="adam", dtype="bfloat16",
    ))
    key = jax.random.key(SEED)
    state = tr.init_state(key)
    r = np.random.default_rng(SEED)
    lengths = r.integers(seq // 2, seq + 1, (batch,))
    data = {
        "input_ids": jnp.asarray(
            r.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32
        ),
        "attention_mask": jnp.asarray(
            np.arange(seq)[None, :] < lengths[:, None], jnp.int32
        ),
        "labels": jnp.asarray(r.integers(0, classes, (batch,)), jnp.int32),
    }
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, stats = tr.train_step(state, data, key)
        losses.append(float(stats["loss"]))  # the host read ends the step
        times.append(time.perf_counter() - t0)
    step = tr.audit_programs(state, data, key)[0]
    facts = {
        "model": f"BERT {cfg.num_layers}L dim {cfg.dim} heads "
                 f"{cfg.num_heads} vocab {cfg.vocab_size}, {classes} classes",
        "dtype": "bfloat16", "optimizer": "adam", "batch": batch,
        "seq": seq,
        "losses": [round(x, 5) for x in losses],
        "cold_step_s": round(times[0], 3),
        "step_wall_s": [round(t, 4) for t in times[1:]],
        "step_kernels": kernels_in(step["lower"]().compile().as_text()),
        "gates_closed": gate_reasons(),
    }
    failures = []
    if not all(np.isfinite(losses)):
        failures.append(f"non-finite loss in {losses}")
    elif not losses[-1] < losses[0]:
        failures.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    return facts, failures


def _rel_gap(a, b) -> float:
    """|a - b| / |b| in float32: how far a result stands off its
    reference (the kda and mamba phases)."""
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# --------------------------------------------------------------------- kda
# kda_chunked hands its matmuls bf16 operands (q, k, v arrive in bf16 in
# a bf16 step) and keeps sums, the solve and the state in float32; the
# recurrence it is held to runs in float32 throughout. Relative to the
# reference's norm. A state dropped, or decayed wrongly, between chunks
# is off by KDA_CARRIED and more.
KDA_TOL = 0.02
KDA_CARRIED = 0.25
KDA_KERNEL = "tl_kda_fwd"  # one of the harness's closed KERNELS since PR 34


def kda_phase(
    *, rows: int = 2, seq: int = 4096, heads: int = 32, head_dim: int = 128,
) -> tuple[dict, list[str]]:
    """``ops/kda.py::kda_chunked`` against the token-by-token recurrence
    (``benchmark/reference/kimi_linear.py::delta_rule``), forward and
    every gradient, at Kimi-Linear's head shape, with the decay the
    model's own initialisation gives: a rate exp(A_log) in 1..16 a head
    and a step in 1e-3..1e-1 a channel, so a chunk of 64 tokens keeps
    between e^-0.06 and e^-100 of the state, and most of an output comes
    from what earlier chunks handed on. (The benchmark's seeded weights
    decay by e^-45 a chunk: its check barely sees the hand-over, PERF.md
    section 7.) ``state_carried`` says how much: the same call with the
    state forgotten at every chunk's start, against the reference.
    ``kernels`` says whether the chunked call's compiled program holds
    the forward kernel, ``gates_closed`` why it would not."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.kimi_linear import delta_rule
    from tensorlink_tpu.ops.kda import CHUNK, kda_chunked

    B, T, H, d = rows, seq, heads, head_dim
    ks = jax.random.split(jax.random.key(SEED), 8)

    def unit(key):
        x = jax.random.normal(key, (B, T, H, d))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    rate = jax.random.uniform(ks[0], (H, 1), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(
        ks[1], (H, d), minval=np.log(1e-3), maxval=np.log(1e-1)))
    args = (
        unit(ks[2]) * d ** -0.5, unit(ks[3]),
        jax.random.normal(ks[4], (B, T, H, d)),
        # softplus(dt_bias + what the token adds), dt_bias = softplus^-1(dt)
        -rate * jax.nn.softplus(
            dt + jnp.log(-jnp.expm1(-dt))
            + 0.5 * jax.random.normal(ks[5], (B, T, H, d))),
        jax.nn.sigmoid(jax.random.normal(ks[6], (B, T, H))),
    )
    ct = jax.random.normal(ks[7], (B, T, H, d))

    def chunked(q, k, v, g, beta):
        return kda_chunked(
            *(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta)

    def forgetful(*xs):  # every chunk a sequence of its own
        cut = (x.reshape(B * T // CHUNK, CHUNK, *x.shape[2:]) for x in xs)
        return chunked(*cut).reshape(B, T, H, d)

    def both(fn):
        o, grads = jax.jit(jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(o * ct), o))(fn(*a)),
            argnums=range(5), has_aux=True,
        ))(*args)
        return (o[1], *grads)

    want, got = both(delta_rule), both(chunked)
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    gaps = {n: _rel_gap(a, b) for n, a, b in zip(names, got, want)}
    carried = _rel_gap(jax.jit(forgetful)(*args), want[0])
    program = jax.jit(chunked).lower(*args).compile().as_text()
    g = args[3]
    facts = {
        "shape": f"{B} x {T} tokens, {H} heads of {d}, chunks of {CHUNK}",
        # ln of what a chunk keeps of the state, by channel: least, most
        "chunk_log_decay": [
            round(float(CHUNK * g.mean((0, 1)).min()), 3),
            round(float(CHUNK * g.mean((0, 1)).max()), 3),
        ],
        "gaps": {n: round(x, 6) for n, x in gaps.items()},
        "state_carried": round(carried, 4),
        "limits": {"gap": KDA_TOL, "state_carried_at_least": KDA_CARRIED},
        "kernels": [k for k in kernels_in(program) if k == KDA_KERNEL],
        "gates_closed": [
            r for r in gate_reasons() if r.startswith(KDA_KERNEL + ":")
        ],
    }
    failures = [
        f"{n} stands {x:.4f} off the recurrence" for n, x in gaps.items()
        if not x < KDA_TOL
    ]
    if not carried > KDA_CARRIED:
        failures.append(
            f"the state carries {carried:.4f} of the output: this decay "
            "does not test the hand-over between chunks")
    return facts, failures


# ------------------------------------------------------------------- mamba
# selective_scan takes u in bf16 (a bf16 step's) and hands y back in
# bf16; Delta, the decay, the state and the sums are float32 on both
# sides. Relative to the reference's norm. A state dropped, or decayed
# wrongly, between chunks is off by MAMBA_CARRIED and more.
MAMBA_TOL = 0.01
MAMBA_CARRIED = 0.15


def mamba_phase(
    *, rows: int = 4, seq: int = 4096, d_inner: int = 5120, d_state: int = 16,
) -> tuple[dict, list[str]]:
    """``ops/selective_scan.py::selective_scan`` against the
    token-by-token recurrence
    (``benchmark/reference/phi4flash.py::selective_scan``), forward and
    every gradient, at Phi-4-mini-flash's scan shape, with the decay the
    published initialisation gives: A_n = -n for n = 1..N in every
    channel and a step in 1e-3..1e-1 a channel, so a chunk of 16 tokens
    keeps between e^-0.02 and e^-25 of a state, and much of an output
    comes from what earlier chunks handed on. (The benchmark's seeded
    weights put A near -1 and the step near 0.7: e^-11 a chunk, so its
    check sees little of the hand-over and nothing of a long memory,
    PERF.md section 7.)
    ``state_carried`` says how much: the same call with the state
    forgotten at every chunk's start, against the reference.
    ``kernels`` says which of the scan's two kernels the compiled
    gradient holds, ``gates_closed`` why it would not."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.phi4flash import selective_scan as recurrence
    from tensorlink_tpu.ops.pallas.selective_scan import BWD, FWD, NAME, TIME
    from tensorlink_tpu.ops.selective_scan import CHUNK, selective_scan

    B, T, E, N = rows, seq, d_inner, d_state
    ks = jax.random.split(jax.random.key(SEED), 7)
    dt = jnp.exp(jax.random.uniform(
        ks[0], (E,), minval=np.log(1e-3), maxval=np.log(1e-1)))
    args = (
        jax.random.normal(ks[1], (B, T, E)).astype(jnp.bfloat16),
        # softplus(dt_bias + what the token adds), dt_bias = softplus^-1(dt)
        jax.nn.softplus(
            dt + jnp.log(-jnp.expm1(-dt))
            + 0.5 * jax.random.normal(ks[2], (B, T, E))),
        -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (E, N)),
        jax.random.normal(ks[3], (B, T, N)),
        jax.random.normal(ks[4], (B, T, N)),
        # a small skip D, so that the output is the scan's and not D u
        0.1 * jax.random.normal(ks[5], (E,)),
    )
    ct = jax.random.normal(ks[6], (B, T, E))

    def plain(u, *rest):
        return recurrence(u.astype(jnp.float32), *rest)

    def forgetful(u, delta, A, Bm, Cm, D):  # every chunk a sequence of its own
        cut = (
            x.reshape(B * T // CHUNK, CHUNK, x.shape[-1])
            for x in (u, delta, Bm, Cm)
        )
        u, delta, Bm, Cm = cut
        return selective_scan(u, delta, A, Bm, Cm, D).reshape(B, T, E)

    def gradient(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(o * ct), o))(
                fn(*a).astype(jnp.float32)),
            argnums=range(6), has_aux=True,
        ))

    def both(program):
        o, grads = program(*args)
        return (o[1], *grads)

    ours = gradient(selective_scan).lower(*args).compile()
    want, got = both(gradient(plain)), both(ours)
    names = ("y", "du", "ddelta", "dA", "dB", "dC", "dD")
    gaps = {n: _rel_gap(a, b) for n, a, b in zip(names, got, want)}
    carried = _rel_gap(jax.jit(forgetful)(*args), want[0])
    program = ours.as_text()
    g = args[1].mean((0, 1))[:, None] * args[2]  # a token's ln decay [E, N]
    facts = {
        "shape": f"{B} x {T} tokens, {E} channels of {N} states, "
                 f"chunks of {CHUNK} (the kernels' time blocks {TIME})",
        # ln of what a chunk keeps of a state: least, most
        "chunk_log_decay": [
            round(float(CHUNK * g.min()), 3), round(float(CHUNK * g.max()), 3),
        ],
        "gaps": {n: round(x, 6) for n, x in gaps.items()},
        "state_carried": round(carried, 4),
        "limits": {"gap": MAMBA_TOL, "state_carried_at_least": MAMBA_CARRIED},
        "kernels": [
            k for k in (FWD, BWD) if any(
                "tpu_custom_call" in ln and k in ln
                for ln in program.splitlines()
            )
        ],
        "gates_closed": [
            r for r in gate_reasons() if r.startswith(NAME + ":")
        ],
    }
    failures = [
        f"{n} stands {x:.4f} off the recurrence" for n, x in gaps.items()
        if not x < MAMBA_TOL
    ]
    if not carried > MAMBA_CARRIED:
        failures.append(
            f"the state carries {carried:.4f} of the output: this decay "
            "does not test the hand-over between chunks")
    return facts, failures


# --------------------------------------------------------------- multichip
def multichip_phase(
    cfg, *, seq: int = 512, batch: int = 8, steps: int = 3,
    prompts: int = 4, prompt_len: int = 64, new_tokens: int = 32,
) -> tuple[dict, list[str]]:
    """What exists only across chips, on GPT-2 ``cfg`` and four
    devices: ``ShardedTrainer`` over pipe=2 x model=2 against the plain
    ``Trainer`` on device 0, and a model=4 ``InferenceEngine`` against
    the one-device engine. Both must leave parameters on every device."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.config import MeshConfig, TrainConfig
    from tensorlink_tpu.models.gpt2 import GPT2
    from tensorlink_tpu.parallel.engine import ShardedTrainer
    from tensorlink_tpu.parallel.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from tensorlink_tpu.runtime.mesh import make_mesh
    from tensorlink_tpu.train.trainer import Trainer, softmax_cross_entropy

    # ShardedTrainer derives a dropout key from the step when it is
    # given none, the plain Trainer's loss_fn here takes none: the two
    # are only comparable with dropout off
    model = GPT2(dataclasses.replace(cfg, dropout=0.0))
    key = jax.random.key(SEED)
    r = np.random.default_rng(SEED)
    ids = r.integers(0, cfg.vocab_size, (batch, seq + 1))
    data = {
        "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
        "labels": jnp.asarray(ids[:, 1:], jnp.int32),
    }
    tcfg = TrainConfig(batch_size=batch, micro_batches=4, dtype="bfloat16")
    failures: list[str] = []

    mesh = make_mesh(MeshConfig(data=1, pipe=2, model=2))
    sharded = ShardedTrainer(
        mesh, tcfg,
        model.as_pipeline_parts(model.init(key)),
        lambda logits, b: softmax_cross_entropy(logits, b["labels"]),
    )
    state = sharded.init_state()
    sharded_losses, t_sharded = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, stats = sharded.train_step(state, data)
        sharded_losses.append(float(stats["loss"]))
        t_sharded.append(time.perf_counter() - t0)
    train_place = _placement(mesh, state.params, {
        "stages.attn.q.w": ("stages", "attn", "q", "w"),
        "embed.wte.table": ("embed", "wte", "table"),
    })
    failures += _spread_failures("ShardedTrainer", train_place["param_bytes"])
    del state

    plain = Trainer(
        model,
        lambda m, p, b, rng: softmax_cross_entropy(
            m.apply(p, b["input_ids"]), b["labels"]
        ),
        tcfg,
    )
    pstate = plain.init_state(key)
    plain_losses = []
    for _ in range(steps):
        pstate, stats = plain.train_step(pstate, data, key)
        plain_losses.append(float(stats["loss"]))
    del pstate
    gaps = [abs(a - b) for a, b in zip(sharded_losses, plain_losses)]
    if not all(np.isfinite(sharded_losses)) or max(gaps) > LOSS_BAND:
        failures.append(
            f"sharded losses {sharded_losses} leave the band {LOSS_BAND} "
            f"around the one-device {plain_losses}"
        )

    # exact greedy tokens across two shardings need arithmetic that does
    # not depend on how a contraction is split: float32 at the highest
    # matmul precision (in bf16 a tensor-parallel all-reduce reorders
    # enough rounding to flip an argmax between near-tied random logits)
    gen = GenerationConfig(max_new_tokens=new_tokens)
    pr = np.stack(_prompts(cfg.vocab_size, prompts, 0, prompt_len))
    params = model.init(key)
    kw = dict(
        max_len=prompt_len + new_tokens, cache_dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    with jax.default_matmul_precision("highest"):
        tp = InferenceEngine(
            make_mesh(MeshConfig(model=4)), model, params, **kw
        )
        tp_tokens = tp.generate(pr, gen)
        infer_place = _placement(tp.mesh, tp.params, {
            "blocks.0.attn.q.w": ("blocks", "0", "attn", "q", "w"),
            "wte.table": ("wte", "table"),
        })
        one = InferenceEngine(make_mesh(MeshConfig()), model, params, **kw)
        one_tokens = one.generate(pr, gen)
    failures += _spread_failures("InferenceEngine", infer_place["param_bytes"])
    if not np.array_equal(tp_tokens, one_tokens):
        failures.append(
            f"model=4 tokens differ from the one-device engine's in "
            f"{int((tp_tokens != one_tokens).any(axis=1).sum())} of "
            f"{prompts} prompts"
        )

    facts = {
        "model": f"GPT-2 {cfg.num_layers}L dim {cfg.dim} "
                 f"heads {cfg.num_heads} vocab {cfg.vocab_size}",
        "train": {
            "mesh": {"data": 1, "pipe": 2, "model": 2}, "dtype": "bfloat16",
            "dropout": 0.0, "batch": batch, "seq": seq,
            "sharded_losses": [round(x, 5) for x in sharded_losses],
            "one_device_losses": [round(x, 5) for x in plain_losses],
            "loss_band": LOSS_BAND,
            "cold_step_s": round(t_sharded[0], 3),
            "step_wall_s": [round(t, 4) for t in t_sharded[1:]],
            **train_place,
        },
        "infer": {
            "mesh": {"model": 4}, "dtype": "float32",
            "matmul_precision": "highest",
            "tokens": int(tp_tokens.size),
            "tokens_equal": bool(np.array_equal(tp_tokens, one_tokens)),
            **infer_place,
        },
        "gates_closed": gate_reasons(),
    }
    return facts, failures


# -------------------------------------------------------------------- main
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="four chips: only the sharded trainer and the "
             "tensor-parallel engine, and what they are compared with",
    )
    args = ap.parse_args(argv)
    start = device_phase(4 if args.multichip else 1)
    device = {k: start[k] for k in ("platform", "kind", "count")}

    from tensorlink_tpu.models.bert import BertConfig
    from tensorlink_tpu.models.gpt2 import GPT2Config
    from tensorlink_tpu.models.llama import LlamaConfig
    from tensorlink_tpu.runtime.compile_cache import cache_entries

    if args.multichip:
        finish("multichip", *multichip_phase(GPT2Config.small()))
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0

    facts, failures = serve_phase(GPT2Config.small(), max_len=1024)
    # D=768 is lane-aligned: the fused residual+norm kernel must be in
    # the decode program. Head dim 64 is not: the paged kernel's gate
    # closes — and has to have said so.
    if "tl_decode_glue" not in facts["decode_kernels"]:
        failures.append("decode_glue kernel is not in the decode program")
    if "tl_paged_decode" in facts["decode_kernels"]:
        failures.append("paged kernel engaged at head dim 64")
    if not any(g.startswith("paged_decode:") for g in facts["gates_closed"]):
        failures.append("the paged kernel's gate closed without a reason")
    finish("serve", facts, failures)

    full = LlamaConfig.mistral_7b()
    facts, failures = serve_kernel_phase(
        dataclasses.replace(full, num_layers=4), max_len=1024
    )
    facts["reduced"] = {"num_layers": f"{full.num_layers} -> 4"}
    for pools in ("kv_bf16", "kv_int8"):
        if "tl_paged_decode" not in facts[pools]["decode_kernels"]:
            failures.append(
                f"{pools}: the decode program fell to "
                "paged_decode_reference (no tl_paged_decode custom call)"
            )
        if "tl_paged_decode" in facts[pools]["reference_path_kernels"]:
            failures.append(f"{pools}: the reference run used the kernel")
    failures += [
        f"{case} was compared with no kernel in its program"
        for case, held in facts["kernel_errors"].items()
        if not held["kernels"]
    ]
    finish("serve-kernel", facts, failures)

    facts, failures = train_phase(BertConfig.base())
    for k in ("tl_flash_fwd", "tl_flash_bwd_dq", "tl_flash_bwd_dkv"):
        if k not in facts["step_kernels"]:
            failures.append(f"{k} is not in the compiled train step")
    finish("train", facts, failures)

    facts, failures = kda_phase()
    # heads of 128 in whole chunks on one chip: the forward is the kernel
    if KDA_KERNEL not in facts["kernels"]:
        failures.append(f"{KDA_KERNEL} is not in the chunked call's program")
    failures += [f"the gate closed: {r}" for r in facts["gates_closed"]]
    finish("kda", facts, failures)

    from tensorlink_tpu.ops.pallas.selective_scan import BWD, FWD

    facts, failures = mamba_phase()
    # 5,120 channels of 16 states on one chip: both passes are the kernels
    failures += [
        f"{k} is not in the compiled gradient" for k in (FWD, BWD)
        if k not in facts["kernels"]
    ]
    failures += [f"the gate closed: {r}" for r in facts["gates_closed"]]
    finish("mamba", facts, failures)

    # a cold directory must have grown; a warm one (a second run on
    # the same machine) is expected to gain nothing for unchanged
    # programs, and then only has to be there
    before = start["compile_cache_entries"]
    after = cache_entries(start["compile_cache_dir"])
    cold = before == 0
    finish(
        "cache",
        {"dir": start["compile_cache_dir"], "entries_before": before,
         "entries_after": after, "cold": cold},
        [] if (after > before if cold else after >= before)
        else [f"compile-cache entries went {before} -> {after}"],
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
