"""Kimi Delta Attention's recurrence in chunks (gated delta rule with a
per-channel decay; Kimi Team 2025) in plain XLA, backward by autodiff
of the same program; the primal forward as a Pallas kernel where a gate
allows it (the last paragraph).

A head's state S [d_k, d_v] follows, token by token (the definition;
``benchmark/reference/kimi_linear.py::delta_rule`` runs it as written
and the tests hold ``kda_chunked`` to it),

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                                   S_0 = 0

which a 4,096-token ``lax.scan`` would run as 4,096 dependent steps and
whose backward would keep a state a token. ``kda_chunked`` is the same
function in chunks of C = 64 tokens. Inside a chunk, with G the
cumulative sum of g from the chunk's start and u_t = b_t (v_t - k_t^T
Diag(exp(g_t)) S_{t-1}) the value a token really writes,

    (I + b A) U = b V - (b K exp(G)) S_0       (b scales rows)
        A[t, s] = sum_c k[t,c] k[s,c] exp(G[t,c] - G[s,c])   s < t
    O   = (Q exp(G)) S_0 + P U
        P[t, s] = sum_c q[t,c] k[s,c] exp(G[t,c] - G[s,c])   s <= t
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

so a chunk costs one unit-lower-triangular solve (all chunks at once),
and only S goes from chunk to chunk, in a ``lax.scan`` of T / C steps.

No ``exp(-G)`` is ever formed: the decay is per channel, so A and P do
not factor into (K exp(G)) (K exp(-G))^T without overflow once a chunk
has decayed by e^88. A chunk is cut into sub-blocks of 16 tokens. Between
two sub-blocks i > j the exponent is split at R_i, G at sub-block i's
first token: exp(G_t - R_i) exp(R_i - G_s), both factors at most 1 (an
underflow there is an exact 0 of a product that is smaller still).
Within a sub-block the sum over channels is taken as written, with the
exponent masked before the exp: 16 x 16 x d_k multiply-adds a sub-block
on the vector unit, fused into their reduction and recomputed in the
backward pass (``jax.checkpoint``) rather than kept.

Matmul operands are in the dtype q arrives in (bf16 in a bf16 step, as a
TPU's default precision would round f32 operands anyway), sums, G, the
solve and S in float32.

Memory: a row of 4,096 tokens at 32 heads of 128 holds some 1.2 GB of
these intermediates in float32, so the batch goes through a row at a
time (``lax.map``), each row recomputed in the backward pass: what is
kept between the passes is q, k, v, g and beta. The result carries the
name ``KEPT`` (``checkpoint_name``), an identity unless an enclosing
``jax.checkpoint`` has a policy that saves it: under such a one
(``models/kimi_linear.py``'s block remat) o is kept too, 268 MB a layer
at that shape, and the block's recompute holds no pass of the scan. A
layer then runs the forward F, the row's F and the backward B; under a
plain ``jax.checkpoint`` around the caller it would run F three times.

Which pass runs where. The first of those, the step's own forward,
needs no residuals: on a TPU, for heads 128 wide and whole chunks of 64
(``_kernel_path``), it is the Pallas kernel ``tl_kda_fwd``
(``ops/pallas/kda.py``: all rows in one call, a chunk in VMEM at a time,
nothing but o written) behind a ``jax.custom_vjp`` whose residuals are
the call's own inputs. Its backward is this file's program as it stands:
row by row, ``jax.vjp`` of ``_chunked`` without a checkpoint (the rule is
the checkpoint), so one XLA F to linearise, then B. Everywhere else (the
CPU, a mesh XLA partitions, another width or dtype) all three are the
XLA program, and on a TPU the closed gate says why
(``kernel.gate_closed``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tensorlink_tpu.ops.pallas import gate_closed, on_tpu, partitioned_by_xla
from tensorlink_tpu.ops.pallas import kda as kernel

CHUNK = 64
SUB = 16
# the name kda_chunked's result carries, for a remat policy to save
KEPT = "kda_scan_o"


def _mm(spec, a, b, dtype):
    return jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.float32,
    )


@jax.checkpoint
def _within_sub_blocks(q, k, G):
    """sum_c x[t,c] k[s,c] exp(G[t,c] - G[s,c]) for s <= t inside each
    sub-block, x = k and x = q: [..., nb, sb, dk] -> two [..., nb, sb, sb].
    One fused multiply-reduce; nothing of size sb*sb*dk is kept."""
    sb = q.shape[-2]
    low = jnp.tril(jnp.ones((sb, sb), bool))[..., None]
    d = jnp.exp(jnp.where(
        low, G[..., :, None, :] - G[..., None, :, :], -jnp.inf
    )) * k[..., None, :, :]
    return (
        jnp.sum(k[..., :, None, :] * d, -1), jnp.sum(q[..., :, None, :] * d, -1)
    )


def kda_chunked(
    q, k, v, g, beta, *, chunk: int = CHUNK, sub: int = SUB,
    interpret: bool = False,
):
    """The recurrence in chunks of ``chunk`` tokens, each worked in
    ``sub``-blocks, one batch row at a time. A length that is no whole
    number of chunks (of sub-blocks, if shorter than a chunk) is padded
    at its end with tokens that write nothing (k, v, beta 0) and whose
    outputs are cut off: causality keeps them from the rest. q, k, g
    [B,T,H,dk]; v [B,T,H,dv]; beta [B,T,H] -> o [B,T,H,dv] float32.
    ``interpret`` runs the kernel path off the TPU, interpreted."""
    T = q.shape[1]
    pad = -T % (chunk if T > chunk else min(sub, T))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
        return kda_chunked(
            q, k, v, g, beta, chunk=chunk, sub=sub, interpret=interpret
        )[:, :T]
    if _kernel_path(q, k, v, g, beta, chunk, sub, interpret):
        return checkpoint_name(_by_kernel(interpret, q, k, v, g, beta), KEPT)
    row = jax.checkpoint(functools.partial(_chunked, chunk=chunk, sub=sub))
    return checkpoint_name(_row_by_row(row, q, k, v, g, beta), KEPT)


def _row_by_row(row, *xs):
    """``row`` over each batch row of ``xs`` in turn (as a batch of one),
    its results stacked back into batches."""
    out = jax.lax.map(lambda x: row(*x), tuple(
        x.reshape(x.shape[0], 1, *x.shape[1:]) for x in xs
    ))
    return jax.tree.map(lambda o: o.reshape(o.shape[0], *o.shape[2:]), out)


def _kernel_path(q, k, v, g, beta, chunk, sub, interpret) -> bool:
    """Static gate for ``tl_kda_fwd``: silent off the TPU (the XLA path
    is the only one there); on it a refusal records its reason."""
    if not interpret and not on_tpu():
        return False
    closed = functools.partial(gate_closed, kernel.NAME, q=q.shape, v=v.shape)
    if not interpret and (why := partitioned_by_xla()):
        return closed(why)
    if (q.shape[-1], v.shape[-1]) != (kernel.WIDTH, kernel.WIDTH):
        return closed(
            f"heads {q.shape[-1]} / {v.shape[-1]} wide, not {kernel.WIDTH}"
        )
    if (chunk, sub) != (kernel.CHUNK, kernel.SUB) or q.shape[1] % chunk:
        return closed(
            f"{q.shape[1]} tokens in chunks of {chunk}, sub-blocks of {sub}: "
            f"not whole chunks of {kernel.CHUNK} in {kernel.SUB}s"
        )
    dtypes = [x.dtype.name for x in (q, k, v, g, beta)]
    if dtypes not in (
        ["bfloat16"] * 3 + ["float32"] * 2, ["float32"] * 5
    ):
        return closed(
            f"operands {dtypes}: q, k, v not all bfloat16 or all float32, "
            "or g, beta not float32"
        )
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _by_kernel(interpret, q, k, v, g, beta):
    return kernel.kda_fwd(q, k, v, g, beta, interpret=interpret)


def _by_kernel_fwd(interpret, *xs):
    # the residuals are the call's own inputs: under a remat that keeps
    # o by name the kernel's call is dead code in the recompute
    return kernel.kda_fwd(*xs, interpret=interpret), xs


def _by_kernel_bwd(interpret, xs, do):
    """The XLA program's backward, a row at a time. No checkpoint around
    ``_chunked``: this rule is one (F to linearise, then B; a checkpoint
    inside would run F twice)."""

    def row(*row_and_do):
        _, vjp = jax.vjp(
            functools.partial(_chunked, chunk=CHUNK, sub=SUB), *row_and_do[:-1]
        )
        return vjp(row_and_do[-1])

    return _row_by_row(row, *xs, do)


_by_kernel.defvjp(_by_kernel_fwd, _by_kernel_bwd)


def _chunked(q, k, v, g, beta, *, chunk: int, sub: int):
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    sb = min(sub, C)
    if C % sb:
        raise ValueError(f"kda_chunked: chunks of {C} are not blocks of {sb}")
    N, nb = T // C, C // sb
    mm, f32 = q.dtype, jnp.float32

    def chunks(x):  # [B,T,H,d] -> [B,H,N,C,d] float32
        return jnp.moveaxis(x.astype(f32), 2, 1).reshape(B, H, N, C, -1)

    q, k, v, g = map(chunks, (q, k, v, g))
    beta = chunks(beta[..., None])  # [B,H,N,C,1]
    G = jnp.cumsum(g, axis=3)

    def blocks(x):  # [B,H,N,C,d] -> [B,H,N,nb,sb,d]
        return x.reshape(B, H, N, nb, sb, x.shape[-1])

    qb, kb, Gb = blocks(q), blocks(k), blocks(G)
    R = Gb[..., :1, :]  # G at each sub-block's first token
    down = jnp.exp(Gb - R)  # t's side: decay since the sub-block began
    # s's side, for sub-block pairs i > j: decay from s to R_i
    earlier = jnp.tril(jnp.ones((nb, nb), bool), -1)[:, :, None, None]
    up = jnp.exp(jnp.where(
        earlier, R[..., :, None, :, :] - Gb[..., None, :, :, :], -jnp.inf
    )) * kb[..., None, :, :, :]  # [B,H,N,nb(i),nb(j),sb,dk]
    up = up.reshape(B, H, N, nb, C, dk)  # (j, s) is the chunk's s
    a_off = _mm("...itc,...isc->...its", kb * down, up, mm)
    p_off = _mm("...itc,...isc->...its", qb * down, up, mm)
    a_in, p_in = _within_sub_blocks(qb, kb, Gb)
    # a sub-block's own square into its place on the chunk's diagonal
    eye = jnp.eye(nb, dtype=f32)[:, None, :, None]
    strictly = jnp.tril(jnp.ones((sb, sb), f32), -1)

    def whole(off, within):
        own = (within[..., :, :, None, :] * eye).reshape(B, H, N, nb, sb, C)
        return (off + own).reshape(B, H, N, C, C)

    A, P = whole(a_off, a_in * strictly), whole(p_off, p_in)

    # (I + beta A) [Wv | Wk] = beta [V | K exp(G)]
    rhs = beta * jnp.concatenate([v, k * jnp.exp(G)], -1)
    W = jax.lax.linalg.triangular_solve(
        jnp.eye(C, dtype=f32) + beta * A, rhs,
        left_side=True, lower=True, unit_diagonal=True,
    )
    Wv, Wk = W[..., :dv], W[..., dv:]
    G_end = G[..., -1:, :]
    # what a step of the scan reads, its matmul operands already in
    # their dtype; both products with S in one: [Wk ; Q exp(G)] S
    xs = (
        Wv, jnp.concatenate([Wk, q * jnp.exp(G)], -2).astype(mm),
        P.astype(mm), (k * jnp.exp(G_end - G)).astype(mm),
        jnp.exp(G_end[..., 0, :]),
    )

    def step(S, x):
        Wv, WkQ, P, Kg, decay = x
        both = _mm("bhck,bhkv->bhcv", WkQ, S, mm)
        U = Wv - both[..., :C, :]
        O = both[..., C:, :] + _mm("bhcs,bhsv->bhcv", P, U, mm)
        S = decay[..., None] * S + _mm("bhck,bhcv->bhkv", Kg, U, mm)
        return S, O

    _, O = jax.lax.scan(
        step, jnp.zeros((B, H, dk, dv), f32),
        tuple(jnp.moveaxis(x, 2, 0) for x in xs),
    )
    # [N,B,H,C,dv] -> [B,T,H,dv]
    return jnp.moveaxis(O, 0, 2).reshape(B, H, T, dv).swapaxes(1, 2)
