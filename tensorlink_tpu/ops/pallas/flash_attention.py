"""Pallas TPU flash-attention: blockwise online-softmax forward AND
blockwise backward (dq, dk, dv) kernels.

The hot op of every transformer in the zoo. Blockwise streaming through
VMEM keeps the [Tq, Tk] score matrix out of HBM: per (batch, head,
q-block) we iterate k-blocks in the innermost grid dimension, carrying the
online-softmax state (m, l, acc) in VMEM scratch that persists across the
innermost iterations.

Forward additionally emits the per-row log-sum-exp (LSE) so the backward
kernels can recompute attention probabilities blockwise (p = exp(s - lse))
without ever materializing the [Tq, Tk] matrix — replacing the O(T^2)
HBM-resident recompute the round-2 backward used (VERDICT weak #4).

Padding masks are supported as a key-validity vector ``kv_mask`` [B, Tk]
(1 = attend, 0 = masked) — exactly the shape of BERT's attention_mask
(reference workload tests/ml/test_full_train.py:85-95 passes HF
attention_mask), so the flagship fine-tune path runs on the kernel.

Grouped-query attention (Hkv < H) is handled by the BlockSpec index maps
(kv block index = h // group): the kernels read the *unrepeated*
[B, Hkv, Tk, D] arrays straight from HBM, so GQA costs no extra HBM
traffic or residual memory. dk/dv come back at H heads and are summed
over each group by the caller (one cheap transient reshape-sum).

Under ``causal=True`` (no window, square blocks) the kernels tell three
kinds of block apart from the block indices alone. Above the diagonal:
skipped, and the BlockSpec index map aims the step at the diagonal
block, so it costs no fetch either. Below it: one unmasked tile, with no
iota, compare or ``where``. On it: worked in sub-tiles (128 wide in the
forward, 256 in the backward kernels), of which only those at or below
the diagonal are computed, n(n+1)/2 of n*n, and only the n squares the
diagonal crosses are masked. At T=1024 with one 1024-block that is 36 of
64 (forward) or 10 of 16 (backward) sub-tiles, 56-62 % of the score
square where causality needs 50 %; the kernels before PR 28 computed
75 % of it at 512-blocks and masked every tile. A ``kv_mask`` keeps its
``where`` on every tile; a window, ``causal=False`` and blocks that are
not square keep the whole-block body.

Sliding-window attention (``window``, Mistral-style) RESTRICTS THE GRID:
for causal windows each q-block's k-loop covers only the
ceil((bq+window)/bk)+1 blocks its band can intersect, with the BlockSpec
index map aiming the DMA at the band (an earlier round measured
predicating compute alone slower than full causal on a v5e: skipped
blocks still paid their HBM fetch). Measured on a v5e in PR 28, bf16 [1, 8, 32768, 128]
W=4096 (the Mistral-7B shape), 1024-blocks, kernels' device time:
forward 2.8x, forward and backward 2.8x over full causal.

Layout: [B, H, T, D] inside the kernels (contiguous lanes along D).
Grids: fwd/dq (B, H, Tq/bq, Tk/bk) with k innermost; dkv
(B, H, Tk/bk, Tq/bq) with q innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# LSE value assigned to fully-masked rows: exp(s - BIG) == 0 for any
# finite score, so backward p/ds vanish exactly where forward emitted 0.
LSE_MASKED = 1e30
LANES = 128


def _band_keep(qi, kj, block_q, block_k, shape, causal: bool,
               window: int | None):
    """Per-block positional keep mask: builds this block's global
    position iotas and delegates the predicate to nn.attention.band_keep
    (ONE home for the band edge convention across reference path,
    fallback, and kernels)."""
    if not causal and window is None:
        return None
    from tensorlink_tpu.nn.attention import band_keep

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return band_keep(q_pos, k_pos, causal, window)


def _block_visible(causal: bool, qi, kj, block_q: int, block_k: int,
                   window: int | None = None):
    """False iff the (qi, kj) block is entirely outside the attended
    region — above the causal diagonal, or (sliding window) entirely
    below the band's lower edge / above its upper edge. Skipping is
    what makes windowed long-seq attention O(T*window), not O(T^2)."""
    vis = True
    if causal:
        vis = kj * block_k <= qi * block_q + block_q - 1
    if window is not None:
        # some (q, k) in the block with k > q - window
        lo = kj * block_k + block_k - 1 > qi * block_q - window
        vis = jnp.logical_and(vis, lo) if vis is not True else lo
        if not causal:  # upper band edge: some k < q + window
            hi = kj * block_k < qi * block_q + block_q - 1 + window
            vis = jnp.logical_and(vis, hi)
    return vis


def _win_lo(qi, block_q: int, block_k: int, window: int):
    """First k-block index visible to q-block ``qi`` under a causal
    sliding window: floor((qi*bq - (window-1)) / bk), clamped to 0.
    Shared by the kernels (actual-kj reconstruction) and the BlockSpec
    index maps (DMA restriction) — one formula, cannot drift."""
    return jnp.maximum((qi * block_q - (window - 1)) // block_k, 0)


def _restricted_index(restricted: bool, start, j_grid, n_full):
    """Shared preamble of the three kernels' restricted-grid mode:
    actual block index = band start + grid-local offset, valid while it
    stays inside the full grid. ``start`` is _win_lo(...) for the
    fwd/dq k-loop and the diagonal block (kj*bk)//bq for the dkv q-loop
    — the two formulas differ, the reconstruction pattern must not."""
    if not restricted:
        return j_grid, True
    actual = start + j_grid
    return actual, actual <= n_full - 1


def _keep_mask(mask_ref, causal, qi, kj, block_q, block_k, shape,
               window: int | None = None):
    """Combined causal/window+padding keep mask for one block
    (None = keep all)."""
    keep = _band_keep(qi, kj, block_q, block_k, shape, causal, window)
    if mask_ref is not None:
        kv_keep = jnp.broadcast_to(mask_ref[0] > 0, shape)  # [1, block_k]
        keep = kv_keep if keep is None else jnp.logical_and(keep, kv_keep)
    return keep


# Sub-tile widths of the diagonal block (_diagonal_tiles), as a v5e
# chose them at [4, 16, 1024, 64] bf16 (PERF.md, PR 28): the forward is
# 29 % faster at 128 than at 256 (36 of 64 tiles against 40, and half
# the masked area), the two backward kernels 4-5 % faster at 256 (their
# matmuls stream 256 rows past each weight tile instead of 128, which
# outweighs the 4 extra tiles).
SUB_TILE_FWD = 128
SUB_TILE_BWD = 256


def _diagonal_aware(causal: bool, window: int | None, block_q: int,
                    block_k: int) -> bool:
    """Static choice of the kernels' body, from what a call fixes at
    trace time. True: causal, no window, square blocks, so that block
    qi == kj is the one the diagonal crosses, corner to corner, and the
    kernels tell the blocks below, on and above it apart. False: the
    whole-block body, for everything else (no diagonal; a band, which
    has its own restricted grid; blocks that are not square)."""
    return causal and window is None and block_q == block_k


def _sub_tile(causal: bool, window: int | None, block_q: int,
              block_k: int, width: int) -> int | None:
    """Width of the sub-tiles the diagonal block is worked in (None:
    the whole-block body, see _diagonal_aware)."""
    if not _diagonal_aware(causal, window, block_q, block_k):
        return None
    for sub in (width, LANES):
        if block_q % sub == 0:
            return sub
    return block_q  # a short sequence's single block: one masked tile


def _diagonal_tiles(block: int, sub: int, mirror: bool = False):
    """The sub-tiles of the diagonal block that causality leaves, as
    ``[(strip, [(span, on_diagonal), ...]), ...]`` of static slices:
    n(n+1)/2 of the n*n, n = block/sub. A strip is ``sub`` q rows and
    its spans the k columns before it (whole, no mask) and its own (the
    square the diagonal crosses); mirrored, for the dkv kernel, a strip
    is ``sub`` k columns and its spans its own q rows and those below."""
    out = []
    for i in range(block // sub):
        strip = pl.ds(i * sub, sub)
        before, after = i * sub, block - (i + 1) * sub
        if mirror:
            rest = [(pl.ds((i + 1) * sub, after), False)] if after else []
            out.append((strip, [(strip, True)] + rest))
        else:
            rest = [(pl.ds(0, before), False)] if before else []
            out.append((strip, rest + [(strip, True)]))
    return out


def _tile_keep(mask_ref, cols, on_diagonal: bool, shape):
    """Keep mask of one tile of the diagonal-aware body (None = keep
    all): the lower triangle on a diagonal square (local positions do:
    the block's and the sub-tile's offsets are the same for q and k
    there), the padding vector's columns where a call has one, nothing
    at all below the diagonal: no iota, no compare, no ``where``."""
    keep = None
    if on_diagonal:
        from tensorlink_tpu.nn.attention import band_keep

        keep = band_keep(
            jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1),
            True, None,
        )
    if mask_ref is not None:
        kv_keep = jnp.broadcast_to(mask_ref[0, :, cols] > 0, shape)
        keep = kv_keep if keep is None else jnp.logical_and(keep, kv_keep)
    return keep


def _visit(body, *, mirror: bool, width: int, causal: bool,
           window: int | None, block_q: int, block_k: int, qi, kj,
           in_range, mask_ref):
    """Run ``body(strip, [(span, keep | None), ...])`` over the score
    tiles of grid step (qi, kj); a strip is q rows and a span k columns,
    or the reverse under ``mirror`` (the dkv kernel). Whole-block body
    (_diagonal_aware False): one tile, the block, if it is visible, with
    the full band and padding mask. Else the block is recognised from
    its indices, as _block_visible recognises a skipped one: below the
    diagonal one unmasked tile, on it the tiles of _diagonal_tiles
    (``width`` wide), above it nothing."""
    strip_len, span_len = (block_k, block_q) if mirror else (block_q, block_k)
    strip, span = pl.ds(0, strip_len), pl.ds(0, span_len)
    sub = _sub_tile(causal, window, block_q, block_k, width)
    if sub is None:
        vis = _block_visible(causal, qi, kj, block_q, block_k, window)
        if in_range is not True:
            vis = jnp.logical_and(in_range, vis)

        @pl.when(vis)
        def _whole():
            body(strip, [(span, _keep_mask(
                mask_ref, causal, qi, kj, block_q, block_k,
                (block_q, block_k), window,
            ))])
        return

    def keep_of(strip, span, on_diagonal):
        rows, cols = (span, strip) if mirror else (strip, span)
        return _tile_keep(
            mask_ref, cols, on_diagonal, (rows.size, cols.size))

    @pl.when(kj < qi)
    def _below():
        body(strip, [(span, keep_of(strip, span, False))])

    @pl.when(kj == qi)
    def _diagonal():
        for strip_, spans in _diagonal_tiles(strip_len, sub, mirror):
            body(strip_, [
                (span_, keep_of(strip_, span_, diag)) for span_, diag in spans
            ])


def _f32(ref, span):
    """Rows ``span`` of a [1, 1, block, D] operand block, f32."""
    return ref[0, 0, span, :].astype(jnp.float32)


def _qk(a, b):
    """a @ b.T with f32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _p_ds(qs, k, v, do, lse, delta, keep):
    """Shared backward-side recompute of one score tile: p = exp(s -
    lse) with s from the SCALED q, as the forward computes it (so it is
    the forward's s to the bit), zero where ``keep`` says, and ds = p *
    (dp - delta) WITHOUT the softmax scale: dk gets it through ``qs``,
    dq once a q-block at its finalize. f32."""
    p = jnp.exp(_qk(qs, k) - lse)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    return p, p * (_qk(do, v) - delta)


# --------------------------------------------------------------- forward
def _flash_fwd_kernel(
    *refs,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_mask: bool,
    window: int | None = None,
    win_grid_nk: int | None = None,  # set = windowed-causal restricted
    nk_full: int | None = None,      # grid (see flash_attention_fwd_lse)
):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        mask_ref = None
    qi = pl.program_id(2)
    j_grid = pl.program_id(3)  # grid-local: init/finalize key on THIS
    nk = pl.num_programs(3)
    # restricted grid: program 3 indexes an offset into the band's
    # k-block range; reconstruct the ACTUAL k-block index (the same
    # formula the BlockSpec index map used to aim the DMA)
    kj, in_range = _restricted_index(
        win_grid_nk is not None,
        _win_lo(qi, block_q, block_k, window) if win_grid_nk is not None
        else 0,
        j_grid, nk_full,
    )
    # a row of a pure causal call sees key 0 in the first block it
    # visits, so its running max is finite from then on and exp(NEG_INF
    # - m) is an exact 0. Only padding or a band can leave a row of a
    # visited block without a key: only there are masked p zeroed again
    rezero = has_mask or window is not None

    @pl.when(j_grid == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def update(rows, tiles):
        """One online-softmax step of q rows ``rows`` over the score
        tiles ``[(k columns, keep | None), ...]``."""
        q = _f32(q_ref, rows) * scale
        ss = []
        for cols, keep in tiles:
            s = _qk(q, _f32(k_ref, cols))  # [rows, cols]
            ss.append(s if keep is None else jnp.where(keep, s, NEG_INF))
        m_prev = m_scr[rows, 0:1]  # [rows, 1]
        m_new = m_prev
        for s in ss:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)  # rescale of old accumulators
        l_new = alpha * l_scr[rows, 0:1]
        acc = acc_scr[rows, :] * alpha
        for s, (cols, keep) in zip(ss, tiles):
            p = jnp.exp(s - m_new)
            if rezero and keep is not None:
                p = jnp.where(keep, p, 0.0)
            l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                p, _f32(v_ref, cols), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        acc_scr[rows, :] = acc
        m_scr[rows, :] = jnp.broadcast_to(m_new, (rows.size, LANES))
        l_scr[rows, :] = jnp.broadcast_to(l_new, (rows.size, LANES))

    _visit(
        update, mirror=False, width=SUB_TILE_FWD, causal=causal,
        window=window, block_q=block_q, block_k=block_k, qi=qi, kj=kj,
        in_range=in_range, mask_ref=mask_ref,
    )

    @pl.when(j_grid == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse rides a 1-lane trailing dim: Mosaic requires the last two
        # block dims (divisible by 8, 128) or equal to the array dims —
        # [block_q, 1] satisfies that at 1/128th the memory of the
        # 128-lane padding jax's own kernel uses
        lse_ref[0, 0] = jnp.where(
            l > 0.0, m_scr[:, 0:1] + jnp.log(l_safe), LSE_MASKED
        )


def _check_shapes(q, k, v, kv_mask):
    B, H, Tq, D = q.shape
    Bk, Hkv, Tk, Dk = k.shape
    # v may have a head size of its own (MLA: q, k 192 wide, v 128):
    # the kernels' bodies take every width from their operands
    if k.shape[:3] != v.shape[:3] or Bk != B or Dk != D:
        raise ValueError(f"bad kv shapes q={q.shape} k={k.shape} v={v.shape}")
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    if kv_mask is not None and kv_mask.shape != (B, Tk):
        raise ValueError(f"kv_mask {kv_mask.shape} != {(B, Tk)}")
    return B, H, Hkv, Tq, Tk, D, v.shape[3]


def _check_blocks(Tq, Tk, block_q, block_k):
    if Tq % block_q or Tk % block_k:
        raise ValueError(
            f"block sizes ({block_q},{block_k}) must divide "
            f"sequence lengths ({Tq},{Tk})"
        )


# The two entry points are jitted so that a model's layers, which call
# them with the same shapes and statics, share ONE trace and lowering of
# each kernel: the unrolled sub-tile bodies take some 60 ms of Python to
# trace, and a 24-layer step program traced them 72 times, every run,
# before its compile-cache lookup (PERF.md, PR 28: 14 s of set-up).
_STATICS = ("causal", "block_q", "block_k", "interpret", "window")


@functools.partial(jax.jit, static_argnames=_STATICS)
def flash_attention_fwd_lse(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D] (Hkv divides H: GQA read via index map)
    v: jax.Array,
    kv_mask: jax.Array | None = None,  # [B, Tk] f32/bool, nonzero = attend
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int | None = None,  # sliding-window band (see _band_keep)
) -> tuple[jax.Array, jax.Array]:
    """-> (o [B,H,Tq,Dv], lse [B,H,Tq] f32)."""
    B, H, Hkv, Tq, Tk, D, Dv = _check_shapes(q, k, v, kv_mask)
    group = H // Hkv
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    _check_blocks(Tq, Tk, block_q, block_k)
    scale = D ** -0.5
    nk_full = Tk // block_k
    # windowed causal: only ceil((bq + window)/bk)+1 k-blocks can
    # intersect a q-block's band — restrict the GRID (and with it the
    # k/v block DMA) to that range instead of predicating compute only:
    # under pl.when alone a skipped block still pays its HBM fetch.
    win_nk = None
    if window is not None and causal and nk_full > 1:
        win_nk = min(nk_full, (block_q + window + block_k) // block_k + 1)
    grid_nk = win_nk if win_nk is not None else nk_full
    grid = (B, H, Tq // block_q, grid_nk)

    diagonal = _diagonal_aware(causal, window, block_q, block_k)

    def kv_block(i, j):
        if diagonal:
            # a block above the diagonal runs nothing: aim its step at
            # the diagonal block, which is already there, and Pallas
            # skips the fetch
            return jnp.minimum(j, i)
        if win_nk is None:
            return j
        return jnp.minimum(
            _win_lo(i, block_q, block_k, window) + j, nk_full - 1
        )

    kernel = functools.partial(
        _flash_fwd_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        has_mask=kv_mask is not None,
        window=window,
        win_grid_nk=win_nk,
        nk_full=nk_full,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, i, j: (b, h // group, kv_block(i, j), 0)),
        pl.BlockSpec((1, 1, block_k, Dv),
                     lambda b, h, i, j: (b, h // group, kv_block(i, j), 0)),
    ]
    args = [q, k, v]
    if kv_mask is not None:
        # kv_mask rides a middle singleton dim ([B, 1, Tk]) so the block's
        # last two dims (1, block_k) satisfy Mosaic's tiling rule
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, h, i, j: (b, 0, kv_block(i, j))
        ))
        args.append(kv_mask.astype(jnp.float32)[:, None, :])
    o, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="tl_flash_fwd",
    )(*args)
    return o, lse[..., 0]


def flash_attention_fwd(q, k, v, kv_mask=None, **kw) -> jax.Array:
    """Forward only (o); kept as the simple public entry."""
    return flash_attention_fwd_lse(q, k, v, kv_mask, **kw)[0]


# -------------------------------------------------------------- backward
# dq kernel: grid (B, H, nq, nk), k innermost; accumulates dq over k
# blocks in VMEM scratch. p is recomputed from (q, k, lse).
def _flash_bwd_dq_kernel(
    *refs,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_mask: bool,
    window: int | None = None,
    win_grid_nk: int | None = None,
    nk_full: int | None = None,
):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        mask_ref = None
    qi = pl.program_id(2)
    j_grid = pl.program_id(3)
    nk = pl.num_programs(3)
    kj, in_range = _restricted_index(
        win_grid_nk is not None,
        _win_lo(qi, block_q, block_k, window) if win_grid_nk is not None
        else 0,
        j_grid, nk_full,
    )

    @pl.when(j_grid == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def accumulate(rows, tiles):
        """dq of q rows ``rows`` from the score tiles ``[(k columns,
        keep | None), ...]``."""
        qs = _f32(q_ref, rows) * scale
        do = _f32(do_ref, rows)
        lse = lse_ref[0, 0, rows, :]  # [rows, 1]
        delta = delta_ref[0, 0, rows, :]
        dq = dq_scr[rows, :]
        for cols, keep in tiles:
            k = _f32(k_ref, cols)
            _, ds = _p_ds(qs, k, _f32(v_ref, cols), do, lse, delta, keep)
            dq = dq + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dq_scr[rows, :] = dq

    _visit(
        accumulate, mirror=False, width=SUB_TILE_BWD, causal=causal,
        window=window, block_q=block_q, block_k=block_k, qi=qi, kj=kj,
        in_range=in_range, mask_ref=mask_ref,
    )

    @pl.when(j_grid == nk - 1)
    def _finalize():
        # the scale that ds lacks (_p_ds), once a q-block
        dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


# dk/dv kernel: grid (B, H, nk, nq), q innermost; accumulates dk and dv
# over q blocks in VMEM scratch. Emits per-H-head dk/dv; the wrapper sums
# GQA groups.
def _flash_bwd_dkv_kernel(
    *refs,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_mask: bool,
    window: int | None = None,
    win_grid_nq: int | None = None,
    nq_full: int | None = None,
):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        mask_ref = None
    kj = pl.program_id(2)
    i_grid = pl.program_id(3)
    nq = pl.num_programs(3)
    # causal: q-blocks below the k-block see nothing — start at the
    # diagonal block (kj*bk // bq); the band's upper edge bounds the
    # range at (bk + window) positions
    qi, in_range = _restricted_index(
        win_grid_nq is not None, (kj * block_k) // block_q, i_grid, nq_full,
    )

    @pl.when(i_grid == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def accumulate(cols, tiles):
        """dk, dv of k rows ``cols`` from the score tiles ``[(q rows,
        keep | None), ...]``."""
        k = _f32(k_ref, cols)
        v = _f32(v_ref, cols)
        dk = dk_scr[cols, :]
        dv = dv_scr[cols, :]
        for rows, keep in tiles:
            qs = _f32(q_ref, rows) * scale
            do = _f32(do_ref, rows)
            p, ds = _p_ds(
                qs, k, v, do, lse_ref[0, 0, rows, :],
                delta_ref[0, 0, rows, :], keep,
            )
            # dv += p^T @ do; dk += ds^T @ (scale * q)
            dv = dv + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk = dk + jax.lax.dot_general(
                ds, qs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dk_scr[cols, :] = dk
        dv_scr[cols, :] = dv

    _visit(
        accumulate, mirror=True, width=SUB_TILE_BWD, causal=causal,
        window=window, block_q=block_q, block_k=block_k, qi=qi, kj=kj,
        in_range=in_range, mask_ref=mask_ref,
    )

    @pl.when(i_grid == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATICS)
def flash_attention_bwd(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]
    v: jax.Array,
    o: jax.Array,  # forward output [B, H, Tq, Dv]
    lse: jax.Array,  # [B, H, Tq] f32 from flash_attention_fwd_lse
    do: jax.Array,  # upstream cotangent of o
    kv_mask: jax.Array | None = None,
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise dq [B,H,Tq,D], dk [B,Hkv,Tk,D], dv [B,Hkv,Tk,Dv]. f32
    accumulation, outputs in input dtype; GQA groups summed here."""
    B, H, Hkv, Tq, Tk, D, Dv = _check_shapes(q, k, v, kv_mask)
    group = H // Hkv
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    _check_blocks(Tq, Tk, block_q, block_k)
    scale = D ** -0.5

    # delta_i = rowsum(do * o): cheap elementwise, XLA fuses it; feeds
    # ds = p * (dp - delta) in both kernels. lse/delta ride a 1-lane
    # trailing dim (see _finalize note).
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    lse = lse[..., None]

    nk_full = Tk // block_k
    nq_full = Tq // block_q
    win_nk = win_nq = None
    if window is not None and causal:
        # same grid restriction as the forward (see its comment)
        if nk_full > 1:
            win_nk = min(nk_full, (block_q + window + block_k) // block_k + 1)
        if nq_full > 1:
            win_nq = min(nq_full, (block_k + window + block_q) // block_q + 1)

    diagonal = _diagonal_aware(causal, window, block_q, block_k)

    def kv_block(i, j):  # dq grid: i = q-block, j = band offset
        if diagonal:  # no fetch above the diagonal, as in the forward
            return jnp.minimum(j, i)
        if win_nk is None:
            return j
        return jnp.minimum(
            _win_lo(i, block_q, block_k, window) + j, nk_full - 1
        )

    def q_block(j, i):  # dkv grid: j = k-block, i = band offset
        if diagonal:  # nor for the q-blocks before the diagonal one
            return jnp.minimum(jnp.maximum(i, j), nq_full - 1)
        if win_nq is None:
            return i
        return jnp.minimum((j * block_k) // block_q + i, nq_full - 1)

    def qspec_of(d):
        return pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0))

    def kspec_of(d):
        return pl.BlockSpec(
            (1, 1, block_k, d),
            lambda b, h, i, j: (b, h // group, kv_block(i, j), 0))

    qspec = qspec_of(D)
    rowq = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))
    common = dict(
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        has_mask=kv_mask is not None, window=window,
    )
    args = [q, k, v, do, lse, delta]
    in_specs = [qspec, kspec_of(D), kspec_of(Dv), qspec_of(Dv), rowq, rowq]
    if kv_mask is not None:
        args.append(kv_mask.astype(jnp.float32)[:, None, :])
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, h, i, j: (b, 0, kv_block(i, j))
        ))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, win_grid_nk=win_nk, nk_full=nk_full,
            **common,
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(B, H, nq_full, win_nk if win_nk is not None else nk_full),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="tl_flash_bwd_dq",
    )(*args)

    # dkv grid swaps the outer two block axes: (b, h, kj, qi)
    def qspec2(d):
        return pl.BlockSpec(
            (1, 1, block_q, d), lambda b, h, j, i: (b, h, q_block(j, i), 0))

    def kspec2(d):
        return pl.BlockSpec(
            (1, 1, block_k, d), lambda b, h, j, i: (b, h // group, j, 0))

    def hspec2(d):
        return pl.BlockSpec((1, 1, block_k, d), lambda b, h, j, i: (b, h, j, 0))

    rowq2 = pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, j, i: (b, h, q_block(j, i), 0))
    in_specs2 = [qspec2(D), kspec2(D), kspec2(Dv), qspec2(Dv), rowq2, rowq2]
    if kv_mask is not None:
        in_specs2.append(pl.BlockSpec((1, 1, block_k), lambda b, h, j, i: (b, 0, j)))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, win_grid_nq=win_nq, nq_full=nq_full,
            **common,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk, Dv), v.dtype),
        ),
        grid=(B, H, nk_full, win_nq if win_nq is not None else nq_full),
        in_specs=in_specs2,
        out_specs=(hspec2(D), hspec2(Dv)),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="tl_flash_bwd_dkv",
    )(*args)
    if group > 1:  # sum each GQA group back to its kv head
        dk = dk.reshape(B, Hkv, group, Tk, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, group, Tk, Dv).sum(axis=2)
    return dq, dk, dv
