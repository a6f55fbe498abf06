"""Pallas TPU kernel for the forward pass of ``ops/kda.py``'s chunked
recurrence: q, k, v, g, beta in, o out, nothing else to HBM.

The grid is (rows, groups of ``HEADS`` heads, chunks), the chunk axis
innermost and ``"arbitrary"``: a group's states S (kept transposed,
[d_v, d_k] float32, so that a chunk's decay scales lanes) live in VMEM
scratch from a row's first chunk to its last. The operands are read as
``nn/kda.py`` hands them over, [B, T, H, d] seen as [B, T, H*d]: a head's
chunk is the (64, 128) tile at column block h, so no [B,H,N,C,d] copy is
made. beta's tile holds every head of the chunk ([64, H]); a head's
column is picked by a masked sum.

One chunk of one head, all in VMEM, in the XLA path's arithmetic (matmul
operands in the dtype q arrives in; sums, G, the solve and S float32):

* G by a lower-triangular ones matmul, g split in three bfloat16 (the
  ones are exact), which is float32's full precision;
* A and P between sub-blocks of 16 by the split exponent
  ``exp(G_t - R_i) exp(R_i - G_s)``, one [32, 128] x [128, 64] matmul a
  sub-block row, the exponent masked before the ``exp``;
* A and P on the diagonal's 16 x 16 squares on the vector unit, column
  by column: the 8 x 128 tiles of the four sub-blocks' upper halves go
  through as one [32, 128] array, the lower halves as another, and the
  upper halves skip the columns that lie wholly above the diagonal;
* ``(I + beta A)^-1`` whole, in float32 at full precision and exactly:
  the four diagonal blocks' inverses as one block-diagonal matrix
  ``(I - N)(I + N^2)(I + N^4)(I + N^8)`` (N strictly lower in blocks of
  16, so N^16 = 0), then two merges ``D - D C D`` (C the part of beta A
  between the blocks D inverts, for blocks of 32 and of 64: exact, since
  C D C = 0) -- ten 64 x 64 matmuls, then one onto ``beta [V | K exp(G)]``.
  Each is the six bfloat16 products that make a float32 product at full
  precision (``_exactly``; ``Precision.HIGHEST`` runs the same six, a pass
  of the matrix unit each), two to a pass: a contraction of 64 fills half
  of the 128 rows of weights a pass holds, so two products sit side by
  side (eight unrolled heads at the Kimi cell's shape: 10.1 ms a layer
  with ``HIGHEST``, 8.2 so; PERF.md, PR 32);
* ``U = Wv - Wk S``, ``O = (Q exp(G)) S + P U``,
  ``S <- Diag(exp(G_C)) S + (K exp(G_C - G))^T U``.

No ``exp(-G)`` and no factor above 1 is formed. A head's chain of small
dependent matmuls would leave the matrix units idle (one head a grid
step: 19.3 ms a layer at [4, 4096, 32, 128] bfloat16 on a v5e, PR 32), so
a grid step works ``HEADS`` heads: ``_head`` is written for one and
``jax.vmap`` batches it, which issues every operation for all the heads
before the next one, and the independent solves interleave. Eight heads:
10.4 ms a layer (four 11.2; sixteen do not fit VMEM). Unrolling the eight
heads in Python with their stages issued in turn read 8.2 ms, but its
body of 16,000 equations cost every process 3.2 s more to trace and
lower, and the benchmark's ``setup_s`` pays that on every start.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "tl_kda_fwd"
CHUNK = 64
SUB = 16
WIDTH = 128  # d_k = d_v: one lane tile
HEADS = 8  # heads a grid step, where that divides H
TILE = 8  # float32 rows a vector register holds
F32 = jnp.float32
NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims=NN):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=F32
    )


def _split(x):
    """float32 -> three bfloat16 whose sum is x to 24 bits."""
    parts = []
    for _ in range(3):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(F32)
    return parts


def _exactly():
    """``mm(a, b)``: a @ b at full float32 precision for a contraction
    of CHUNK, as the six bfloat16 products that make it (a1 b1, a2 b1,
    a1 b2, a3 b1, a2 b2, a1 b3), two to a pass of the matrix unit: side
    by side in the 128 rows of weights a pass holds. An operand met
    again is split once."""
    seen = []

    def parts(x):
        for y, split in seen:
            if y is x:
                return split
        seen.append((x, _split(x)))
        return seen[-1][1]

    def two(x, y, u, w):  # x u + y w
        return _dot(jnp.concatenate([x, y], 1), jnp.concatenate([u, w]))

    def mm(a, b):
        (a1, a2, a3), (b1, b2, b3) = parts(a), parts(b)
        return two(a2, a1, b2, b3) + two(a3, a1, b1, b2) + two(a1, a2, b1, b1)

    return mm


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _halves(x):
    """[CHUNK, d] -> the sub-blocks' upper halves (tokens 0-7 of each)
    and their lower halves, each [CHUNK / 2, d]: whole float32 tiles."""
    tiles = [x[i:i + TILE] for i in range(0, CHUNK, TILE)]
    return jnp.concatenate(tiles[0::2]), jnp.concatenate(tiles[1::2])


def _row_of_each(x, s):
    """Token s of every sub-block over that sub-block's half:
    [CHUNK, d] -> [CHUNK / 2, d]."""
    return jnp.concatenate([
        jnp.broadcast_to(x[i + s:i + s + 1], (TILE, x.shape[1]))
        for i in range(0, CHUNK, SUB)
    ])


def _squares(G, kf, qf):
    """The diagonal's sub-block squares of A (strictly lower) and P
    (lower), each in its place in a [CHUNK, CHUNK] of zeros."""
    half = CHUNK // 2
    Gh, kh, qh = _halves(G), _halves(kf), _halves(qf)
    row = _iota((half, CHUNK), 0)
    first = row // TILE * SUB  # a row's sub-block starts at this column
    within = (row % TILE, row % TILE + TILE)  # token in its sub-block
    col = _iota((half, CHUNK), 1)
    wide = _iota((half, WIDTH), 0) % TILE
    a = [jnp.zeros((half, CHUNK), F32)] * 2
    p = [jnp.zeros((half, CHUNK), F32)] * 2
    for s in range(SUB):
        Gs, ks = _row_of_each(G, s), _row_of_each(kf, s)
        here = col == first + s
        for lower in (0, 1) if s < TILE else (1,):
            e = Gh[lower] - Gs
            if lower == s // TILE:  # the diagonal crosses this tile
                e = jnp.where(wide + lower * TILE >= s, e, -jnp.inf)
            d = jnp.exp(e) * ks
            ca = jnp.sum(kh[lower] * d, -1, keepdims=True)
            cp = jnp.sum(qh[lower] * d, -1, keepdims=True)
            a[lower] = jnp.where(here & (within[lower] > s), ca, a[lower])
            p[lower] = jnp.where(here, cp, p[lower])

    def whole(upper, lower):
        return jnp.concatenate([
            x[i:i + TILE] for i in range(0, half, TILE) for x in (upper, lower)
        ])

    return whole(*a), whole(*p)


def _inverse(M, mm):
    """(I + M)^-1 for M [CHUNK, CHUNK] strictly lower, exactly."""
    row, col = _iota(M.shape, 0), _iota(M.shape, 1)
    eye = (row == col).astype(F32)

    def same(block):
        return row // block == col // block

    # the diagonal blocks: (I - N)(I + N^2)(I + N^4)(I + N^8), N^16 = 0
    N = jnp.where(same(SUB), M, 0.0)
    N2 = mm(N, N)
    D = eye - N + N2 - mm(N, N2)
    N4 = mm(N2, N2)
    D = D + mm(D, N4)
    D = D + mm(D, mm(N4, N4))
    block = SUB
    while block < CHUNK:  # blocks of 2 * block from blocks of block
        C = jnp.where(same(2 * block) & ~same(block), M, 0.0)
        D = D - mm(mm(D, C), D)
        block *= 2
    return D


def _head(q, k, v, g, beta, S):
    """One head's chunk: q, k, v, g [CHUNK, WIDTH]; beta [CHUNK, 1]; S
    the head's transposed state [WIDTH, WIDTH] -> o [CHUNK, WIDTH] and
    the state after the chunk."""
    mm = q.dtype
    qf, kf = q.astype(F32), k.astype(F32)
    row, col = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    exact = _exactly()
    g1, g2, g3 = _split(g)
    ones = (col <= row).astype(jnp.bfloat16)  # exact: g alone is split
    G = _dot(ones, g3) + _dot(
        jnp.concatenate([ones, ones], 1), jnp.concatenate([g1, g2])
    )
    eG = jnp.exp(G)
    G_end = G[CHUNK - 1:]
    # between sub-blocks: the exponent split at R_i, sub-block i's first G
    R = jnp.concatenate([
        jnp.broadcast_to(G[i:i + 1], (SUB, WIDTH))
        for i in range(0, CHUNK, SUB)
    ])
    down = jnp.exp(G - R)
    kd, qd = (kf * down).astype(mm), (qf * down).astype(mm)
    token = _iota((CHUNK, WIDTH), 0)
    # sub-block 0 has nothing before it
    a_off, p_off = ([jnp.zeros((SUB, CHUNK), F32)] for _ in range(2))
    for i in range(SUB, CHUNK, SUB):
        up = jnp.exp(jnp.where(token < i, G[i:i + 1] - G, -jnp.inf)) * kf
        both = _dot(
            jnp.concatenate([kd[i:i + SUB], qd[i:i + SUB]]), up.astype(mm), NT
        )
        a_off.append(both[:SUB])
        p_off.append(both[SUB:])
    a_in, p_in = _squares(G, kf, qf)
    A = jnp.concatenate(a_off) + a_in
    P = (jnp.concatenate(p_off) + p_in).astype(mm)
    # (I + beta A) [Wv | Wk] = beta [V | K exp(G)]
    L = _inverse(beta * A, exact)
    W = exact(L, beta * jnp.concatenate([v.astype(F32), kf * eG], 1))
    Wv, Wk = W[:, :WIDTH], W[:, WIDTH:]
    both = _dot(
        jnp.concatenate([Wk, qf * eG]).astype(mm), S.astype(mm), NT
    )
    Um = (Wv - both[:CHUNK]).astype(mm)
    Kg = (kf * jnp.exp(G_end - G)).astype(mm)
    return (
        both[CHUNK:] + _dot(P, Um), jnp.exp(G_end) * S + _dot(Um, Kg, TN)
    )


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref, *, heads):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def lanes(h):
        return slice(h * WIDTH, (h + 1) * WIDTH)

    def by_head(ref):  # [1, CHUNK, heads * WIDTH] -> [heads, CHUNK, WIDTH]
        return jnp.stack([ref[0, :, lanes(h)] for h in range(heads)])

    betas = beta_ref[0]  # [CHUNK, H]: every head's
    head_of = _iota(betas.shape, 1) - pl.program_id(1) * heads
    beta = jnp.stack([
        jnp.sum(jnp.where(head_of == h, betas, 0.0), -1, keepdims=True)
        for h in range(heads)
    ])
    o, s_ref[...] = jax.vmap(_head)(
        by_head(q_ref), by_head(k_ref), by_head(v_ref), by_head(g_ref),
        beta, s_ref[...],
    )
    for h in range(heads):
        o_ref[0, :, lanes(h)] = o[h]


def kda_fwd(q, k, v, g, beta, *, interpret: bool = False):
    """o of the recurrence for whole chunks of ``CHUNK`` tokens and
    heads ``WIDTH`` wide. q, k, v [B,T,H,128] in one dtype (bfloat16 or
    float32); g [B,T,H,128] and beta [B,T,H] float32 -> [B,T,H,128]
    float32."""
    B, T, H, d = q.shape
    if d != WIDTH or v.shape[-1] != WIDTH or T % CHUNK:
        raise ValueError(
            f"tl_kda_fwd: {q.shape} is not whole chunks of {WIDTH}-wide heads"
        )
    o = _flat(*(x.reshape(B, T, H * d) for x in (q, k, v, g)), beta, interpret)
    return o.reshape(B, T, H, d)


@functools.partial(jax.jit, static_argnums=5)
def _flat(q, k, v, g, beta, interpret):
    """The call itself, on [B, T, H*d]: a producer can write that shape
    directly, where a reshape of [B, T, H, d] in memory is a copy.
    Jitted so that a model's layers share one trace and one lowering of
    the kernel's body."""
    B, T, H = beta.shape
    heads = max(n for n in range(1, HEADS + 1) if H % n == 0)
    tile = pl.BlockSpec((1, CHUNK, heads * WIDTH), lambda b, j, n: (b, n, j))
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        out_shape=jax.ShapeDtypeStruct(q.shape, F32),
        grid=(B, H // heads, T // CHUNK),
        in_specs=[tile] * 4 + [
            pl.BlockSpec((1, CHUNK, H), lambda b, j, n: (b, n, 0)),
        ],
        out_specs=tile,
        scratch_shapes=[pltpu.VMEM((heads, WIDTH, WIDTH), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=NAME,
    )(q, k, v, g, beta)
