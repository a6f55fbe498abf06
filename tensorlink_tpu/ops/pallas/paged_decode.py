"""Block-table-native paged-decode attention kernel.

The paged serving path (``nn/attention.py _apply_paged``) addresses KV
through a per-row block table. The pure-XLA form pays a full gather
materialization every step: ``pool[block_table]`` writes a
``[B, Lv, Hkv, D]`` logical view to HBM before attention ever reads it
— at decode (T=1) that copy IS the dominant HBM traffic, the exact
bytes the per-program MBU telemetry (PR 13) bills decode for. This
kernel walks the block table directly instead: one program instance
per (row, query head, KV page), the table lookup happens in the
BlockSpec index map (so each page is DMA'd pool->VMEM once, no view
ever materializes), and pages accumulate through the standard
online-softmax scratch carry (same discipline as
``ops/pallas/flash_attention.py``).

Grid layout ``(B, H, NSUP, G)``, last dim fastest:

- ``B, H``: one (row, query head) pair per scratch lifetime — GQA reads
  the *unrepeated* pools via ``h // group`` index maps, exactly like
  the flash kernels;
- ``NSUP x G``: the row's ``max_blocks`` logical pages, walked
  ``G = pages_per_step`` at a time. Each ``g`` stashes its page's
  masked scores (and dequantized V) in page-major VMEM scratch
  (``[G, T, bs]`` / ``[G, bs, D]``: a page is a leading-dim index — a
  ``bs``-wide dynamic slice of one lane axis is not something the TPU
  compiler takes); the online-softmax rescale runs ONCE per superstep
  over the ``G`` stashed pages — ``G`` is the tunable that amortizes
  rescale overhead over page DMA, the knob ``runtime/autotune.py``
  persists beside the flash blocks.

Pages outside a row's live range (beyond ``lengths[b]``, or wholly
below the sliding-window band) clamp their index map into the live
range — a repeated block index skips the re-DMA — and their scores
mask to ``NEG_INF``, so retired rows and sentinel table entries are
harmless by construction (finite garbage, never attended).

int8 KV: when the pools carry per-slot scales (``k_scale``/``v_scale``
siblings, see ``MultiHeadAttention.init_paged_cache(quant="int8")``),
the kernel dequantizes each page in VMEM — bf16/f32 KV never
materializes at cache width, so decode HBM traffic tracks the int8
bytes.

Conventions follow ``decode_glue.py``: ``TL_PAGED_KERNEL`` kill switch
(``0`` = off, ``1`` = TPU only, ``interpret`` = force the emulated
kernel anywhere — CPU CI parity/bench mode), a jnp reference
implementation as the single home of the math, ``interpret=True``
parity tests off-TPU. Interpret mode emulates the grid serially: fine
for parity and tiny benches, orders of magnitude slower than XLA for
real shapes.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorlink_tpu.ops.pallas import (
    gate_closed,
    on_tpu,
    partitioned_by_xla,
)

LANES = 128
NEG_INF = -1e30  # finite: exp underflows to 0.0, NaN-free (see nn.attention)


def _mode() -> str:
    """Kill-switch state, read at CALL time (tests toggle the env var
    mid-process; an import-time snapshot would make the kill switch a
    restart-only control)."""
    return os.environ.get("TL_PAGED_KERNEL", "1")


# ------------------------------------------------------------- overrides
# per-(max_blocks, block_size) tuned pages-per-step:
# {(max_blocks, block_size | None): pages}. An exact (max_blocks,
# block_size) entry wins over (max_blocks, None); anything else falls
# back to the lane-width heuristic. Mirrors ops/flash.py
# _BLOCK_OVERRIDES — runtime/autotune.py persists/reapplies both under
# the same fingerprint key.
_PAGE_OVERRIDES: dict[tuple[int, int | None], int] = {}


def set_paged_block_override(
    max_blocks: int, pages: int, *, block_size: int | None = None
) -> None:
    """Pin the kernel's pages-per-step for a ``max_blocks``-page view
    (optionally only at ``block_size``).

    Overrides are read at TRACE time, so already-compiled decode
    programs would silently keep their old grid; the jit caches are
    cleared so the next call actually retraces with the tuned value."""
    if pages < 1 or pages > max_blocks:
        raise ValueError(
            f"paged pages-per-step override {pages} outside "
            f"[1, max_blocks={max_blocks}]"
        )
    key = (int(max_blocks), None if block_size is None else int(block_size))
    if _PAGE_OVERRIDES.get(key) == int(pages):
        # already installed at this value: nothing to retrace, and
        # skipping the clear keeps a warm autotune restart from wiping
        # a live sibling engine's jitted programs (ops/flash.py has the
        # same discipline)
        return
    _PAGE_OVERRIDES[key] = int(pages)
    # sanctioned cache clear: overrides are read at trace time
    jax.clear_caches()  # tlint: disable=TL503 tuning must retrace


def clear_paged_block_overrides() -> None:
    if _PAGE_OVERRIDES:
        _PAGE_OVERRIDES.clear()
        # sanctioned: compiled programs baked the old grid in
        jax.clear_caches()  # tlint: disable=TL503 tuning must retrace


def paged_block_overrides() -> list[tuple[int, int | None, int]]:
    """Snapshot of the installed overrides as ``(max_blocks,
    block_size|None, pages)`` rows — the JSON-safe form
    ``runtime/autotune.py`` persists."""
    return sorted(
        ((mb, bsz, pg) for (mb, bsz), pg in _PAGE_OVERRIDES.items()),
        key=lambda t: (t[0], -1 if t[1] is None else t[1], t[2]),
    )


def paged_pages_for(max_blocks: int, block_size: int) -> int:
    """Resolve pages-per-step: exact override, block-size-agnostic
    override, then the heuristic — enough pages that the scratch score
    stripe spans a full ``LANES`` lane (small pages under-utilize the
    VPU rescale otherwise), capped at the view width."""
    for key in ((max_blocks, block_size), (max_blocks, None)):
        if key in _PAGE_OVERRIDES:
            return min(_PAGE_OVERRIDES[key], max_blocks)
    return max(1, min(max_blocks, LANES // max(block_size, 1)))


# ------------------------------------------------------------- reference
def paged_decode_reference(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [NB, bs, Hkv, D] pool (int8 when k_scale given)
    v: jax.Array,  # [NB, bs, Hkv, D]
    block_table: jax.Array,  # [B, MB] i32; NB = unmapped sentinel
    lengths: jax.Array,  # [B] i32 live token count (POST-write: idx + T)
    *,
    k_scale: jax.Array | None = None,  # [NB, bs, Hkv] f32
    v_scale: jax.Array | None = None,
    mask: jax.Array | None = None,  # [B, 1, T|1, Lv] bool, True=attend
    window: int | None = None,
) -> jax.Array:
    """The jnp home of the kernel's math (gather the logical view,
    dequantize, mask in logical coordinates, f32 softmax with the
    zero-normalizer guard) — parity tests pin the kernel against THIS,
    and it is the fallback when the kernel cannot engage."""
    B, T, H, D = q.shape
    NB, bs, Hkv = k.shape[0], k.shape[1], k.shape[2]
    MB = block_table.shape[1]
    Lv = MB * bs
    bt = jnp.minimum(block_table, NB - 1)  # sentinel -> clamped garbage
    kk = k[bt].reshape(B, Lv, Hkv, D).astype(jnp.float32)
    vv = v[bt].reshape(B, Lv, Hkv, D).astype(jnp.float32)
    if k_scale is not None:
        kk = kk * k_scale[bt].reshape(B, Lv, Hkv)[..., None]
        vv = vv * v_scale[bt].reshape(B, Lv, Hkv)[..., None]
    if Hkv != H:
        rep = H // Hkv
        kk = jnp.repeat(kk, rep, axis=2)
        vv = jnp.repeat(vv, rep, axis=2)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kk
    ) * (D ** -0.5)
    kpos = jnp.arange(Lv)[None, None, None, :]
    qpos = (
        lengths[:, None] - T + jnp.arange(T)[None, :]
    )[:, None, :, None]  # [B, 1, T, 1]
    keep = kpos <= qpos
    if window is not None:
        keep = jnp.logical_and(keep, kpos > qpos - window)
    if mask is not None:
        keep = jnp.logical_and(keep, mask)
    keep = jnp.broadcast_to(keep, s.shape)
    s = jnp.where(keep, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(keep, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
    l_q = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1, 3)  # [B, T, H, 1]
    return (o / l_q).astype(q.dtype)


# --------------------------------------------------------------- kernel
def _paged_kernel(
    len_ref, bt_ref, *refs,
    T: int, bs: int, G: int, scale: float,
    window: int | None, quantized: bool, has_mask: bool,
):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    ks_ref = next(it) if quantized else None
    vs_ref = next(it) if quantized else None
    mask_ref = next(it) if has_mask else None
    o_ref, s_scr, v_scr, m_scr, l_scr, acc_scr = it

    b = pl.program_id(0)
    jc, g = pl.program_id(2), pl.program_id(3)
    nsup = pl.num_programs(2)
    j = jc * G + g  # UNCLAMPED logical page: positions must stay honest

    @pl.when(jnp.logical_and(jc == 0, g == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kb = k_ref[0, 0].astype(jnp.float32)  # [bs, D]
    vb = v_ref[0, 0].astype(jnp.float32)
    if quantized:
        kb = kb * ks_ref[0, 0].astype(jnp.float32)  # [bs, 1] broadcasts
        vb = vb * vs_ref[0, 0].astype(jnp.float32)
    qv = q_ref[0, 0].astype(jnp.float32) * scale  # [T, D]
    s = jax.lax.dot_general(
        qv, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [T, bs]
    # positional keep in LOGICAL coordinates — out-of-live pages (the
    # clamped-DMA repeats) mask themselves entirely here, so the body
    # needs no in-range branch at all
    live = len_ref[b]
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (T, bs), 1)
    qpos = live - T + jax.lax.broadcasted_iota(jnp.int32, (T, bs), 0)
    keep = kpos <= qpos
    if window is not None:
        keep = jnp.logical_and(keep, kpos > qpos - window)
    if has_mask:
        keep = jnp.logical_and(keep, mask_ref[0, 0] > 0)
    s = jnp.where(keep, s, NEG_INF)
    s_scr[g] = s
    v_scr[g] = vb

    @pl.when(g == G - 1)
    def _update():
        s_all = s_scr[...]  # [G, T, bs]
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(
            m_prev,
            jnp.max(jnp.max(s_all, axis=0), axis=1, keepdims=True),
        )
        p = jnp.exp(s_all - m_new[None])
        # recover the mask from the score sentinel: when every stripe
        # entry is masked, exp(s - m_new) above is exp(0) = 1, not 0
        p = jnp.where(s_all > NEG_INF * 0.5, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(
            jnp.sum(p, axis=0), axis=1, keepdims=True
        )
        pv = jax.lax.dot_general(
            p, v_scr[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [G, T, D]
        acc_scr[...] = acc_scr[...] * alpha + jnp.sum(pv, axis=0)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jnp.logical_and(jc == nsup - 1, g == G - 1))
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_decode_ok(
    q: jax.Array, k_pool: jax.Array, *,
    mask: jax.Array | None = None, interpret: bool | None = None,
) -> bool:
    """Static gate: can (and should) the kernel serve this call?
    ``TL_PAGED_KERNEL=0`` forces False everywhere — the pure-XLA
    gather path is then bit-for-bit what it was before this kernel
    existed. Off the TPU (and not interpreting) the answer is a silent
    False; past that point every refusal records its reason
    (``ops/pallas gate_closed``)."""
    mode = _mode()
    it = (mode == "interpret") if interpret is None else interpret
    if not it and not on_tpu():
        return False
    closed = partial(
        gate_closed, "paged_decode", q=q.shape, pool=k_pool.shape
    )
    if mode == "0":
        return closed("TL_PAGED_KERNEL=0")
    if not it and (why := partitioned_by_xla()):
        return closed(why)
    D = q.shape[-1]
    if not it and D % LANES:
        return closed(f"head dim {D} is not a multiple of {LANES} lanes")
    if q.shape[2] % k_pool.shape[2]:
        return closed("GQA needs Hkv | H")
    if mask is not None and (mask.ndim != 4 or mask.shape[1] != 1):
        return closed("per-head masks stay on the XLA path")
    return True


def paged_decode_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [NB, bs, Hkv, D] pool (int8 when k_scale given)
    v: jax.Array,
    block_table: jax.Array,  # [B, MB] i32
    lengths: jax.Array,  # [B] i32 POST-write live counts (index + T)
    *,
    k_scale: jax.Array | None = None,  # [NB, bs, Hkv] f32
    v_scale: jax.Array | None = None,
    mask: jax.Array | None = None,  # [B, 1, T|1, Lv] bool
    window: int | None = None,
    pages_per_step: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged-decode attention over the block-table form -> [B, T, H, D].

    ``T >= 1`` (single-step decode or a speculative verify-K chunk:
    query t sits at logical position ``lengths - T + t``). Scale is the
    fixed ``1/sqrt(D)`` — callers with a custom scale stay on the XLA
    path. Falls back to ``paged_decode_reference`` whenever
    ``paged_decode_ok`` says the kernel cannot engage."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    B, T, H, D = q.shape
    NB, bs, Hkv = k.shape[0], k.shape[1], k.shape[2]
    MB = block_table.shape[1]
    Lv = MB * bs
    if mask is not None and mask.shape[-1] != Lv:
        raise ValueError(
            f"paged kernel needs a view-width mask (last dim {Lv}), "
            f"got {mask.shape}"
        )
    it = (_mode() == "interpret") if interpret is None else interpret
    if not paged_decode_ok(q, k, mask=mask, interpret=it):
        return paged_decode_reference(
            q, k, v, block_table, lengths, k_scale=k_scale,
            v_scale=v_scale, mask=mask, window=window,
        )
    group = H // Hkv
    G = pages_per_step or paged_pages_for(MB, bs)
    G = max(1, min(int(G), MB))
    nsup = -(-MB // G)
    quantized = k_scale is not None
    has_mask = mask is not None

    lengths = lengths.astype(jnp.int32)
    bt32 = block_table.astype(jnp.int32)

    def _page(jc, g, len_ref, bt_ref, b):
        """Clamped page for the DMA: pages outside the live range (or
        wholly below the window band) re-aim at an in-range page — a
        repeated block index costs no re-fetch — and sentinel table
        entries clamp into the pool. The kernel body masks by the
        UNCLAMPED logical position, so the clamp is invisible to the
        math."""
        j = jc * G + g
        live = len_ref[b]
        jmax = jnp.maximum(live - 1, 0) // bs
        jmin = 0
        if window is not None:
            jmin = jnp.maximum(live - T - (window - 1), 0) // bs
        je = jnp.clip(j, jmin, jmax)
        return je

    def _q_map(b, h, jc, g, len_ref, bt_ref):
        return (b, h, 0, 0)

    def _kv_map(b, h, jc, g, len_ref, bt_ref):
        je = _page(jc, g, len_ref, bt_ref, b)
        phys = jnp.minimum(bt_ref[b, je], NB - 1)
        return (phys, h // group, 0, 0)

    def _scale_map(b, h, jc, g, len_ref, bt_ref):
        je = _page(jc, g, len_ref, bt_ref, b)
        phys = jnp.minimum(bt_ref[b, je], NB - 1)
        return (phys, h // group, 0, 0)

    def _mask_map(b, h, jc, g, len_ref, bt_ref):
        return (b, _page(jc, g, len_ref, bt_ref, b), 0, 0)

    # head-major layouts (flash-kernel convention: the last two block
    # dims equal the array dims, so tiny decode shapes tile legally)
    qT = q.transpose(0, 2, 1, 3)  # [B, H, T, D]
    kT = k.transpose(0, 2, 1, 3)  # [NB, Hkv, bs, D]
    vT = v.transpose(0, 2, 1, 3)
    in_specs = [
        pl.BlockSpec((1, 1, T, D), _q_map),
        pl.BlockSpec((1, 1, bs, D), _kv_map),
        pl.BlockSpec((1, 1, bs, D), _kv_map),
    ]
    args = [qT, kT, vT]
    if quantized:
        for sc in (k_scale, v_scale):
            in_specs.append(pl.BlockSpec((1, 1, bs, 1), _scale_map))
            args.append(
                sc.transpose(0, 2, 1)[..., None].astype(jnp.float32)
            )
    if has_mask:
        # page-major [B, MB, T, bs]: a (T, bs) block then spans the
        # array's last two dims (a bs-wide slice of the Lv lane axis
        # is not a legal TPU block)
        in_specs.append(pl.BlockSpec((1, 1, T, bs), _mask_map))
        args.append(
            jnp.broadcast_to(mask, (B, 1, T, Lv)).astype(jnp.float32)
            .reshape(B, T, MB, bs).transpose(0, 2, 1, 3)
        )
    kernel = partial(
        _paged_kernel, T=T, bs=bs, G=G, scale=D ** -0.5,
        window=window, quantized=quantized, has_mask=has_mask,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nsup, G),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, T, D), _q_map),
        scratch_shapes=[
            pltpu.VMEM((G, T, bs), jnp.float32),  # score stripe
            pltpu.VMEM((G, bs, D), jnp.float32),  # dequantized V stripe
            pltpu.VMEM((T, LANES), jnp.float32),   # running max
            pltpu.VMEM((T, LANES), jnp.float32),   # running normalizer
            pltpu.VMEM((T, D), jnp.float32),       # output accumulator
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qT.shape, q.dtype),
        interpret=it,
        name="tl_paged_decode",
    )(lengths, bt32, *args)
    return o.transpose(0, 2, 1, 3)
