"""Pallas TPU kernels for ``ops/selective_scan.py``'s recurrence, forward
(``tl_mamba_scan_fwd``) and backward (``tl_mamba_scan_bwd``): the state
lives in VMEM from a row's first token to its last, and no per-token
state reaches HBM in either pass.

Layout. The operands are read as ``nn/mamba.py`` hands them over: u
[B, T, d] in its dtype, Delta [B, T, d] float32, B and C [B, T, N], A^T
[N, d] and D [1, d] float32. A channel's state is a column of the
[N, channels] tile the kernels carry, the channels along the lanes (as
the XLA path lays the state out); Delta u is formed in VMEM.

Grid: (rows, blocks of channels, blocks of ``TIME`` tokens), the time
axis innermost and ``"arbitrary"``. A grid step goes through its tokens
``GROUP`` at a time: it reads the group's rows of u, Delta, B and C as
whole tiles and stages them in VMEM, Delta and Delta u as rows [GROUP,
channels], B and C as columns [N, GROUP]. Then per token it updates the
state

    s <- exp(Delta_t A) * s + (Delta_t u_t) B_t       y_t = sum_n s C_t

with Delta_t, u_t a row broadcast over the N sublanes and B_t, C_t a
column broadcast over the lanes. y_t goes to a row of VMEM scratch; the
group's [GROUP, channels] tile of y + D u is written in u's dtype.

The tokens of a group are a ``fori_loop`` unrolled whole: its body is
traced once, and lowered once a token with the token's index a constant,
which the column of B and C needs (Mosaic takes no lane offset it cannot
prove). With the tokens written out in Python instead, each token's
operations were traced apart, and the Phi cell's first step took 12.2-13.5
s on a v5e host for the XLA path's 9.1-9.3 (unrolled: 8.7-9.0). A loop
that is not unrolled lowers the body once, but runs at half the speed.

Residual: the forward also writes the state entering each block of
``TIME`` tokens, [B, T / TIME, N, d] float32 (84 MB at [4, 4096, 5120],
N = 16, against the XLA path's 335 MB a chunk of 16). The backward goes
over a row's time blocks in reverse. For each it first runs the block
forward again from its entry state and keeps every token's state in VMEM
([TIME + 1, N, channels] float32), then goes back token by token with the
cotangent ds of the state:

    ds_t  = C_t dy_t + exp(Delta_{t+1} A) ds_{t+1}
    dC_t  = sum_c s_t dy_t            dB_t = sum_c ds_t Delta_t u_t
    dx_t  = sum_n ds_t B_t            (x = Delta u: du, dDelta from it)
    q_t   = ds_t s_{t-1} exp(Delta_t A)
    dA   += q_t Delta_t               dDelta_t += sum_n q_t A

ds_{t+1}'s decayed part crosses from block to block in scratch; dA and
dD add up over a row's time axis in VMEM. dB and dC leave as sums over a
block of channels, [B, d / channels, N, T] (the tokens along the lanes),
and XLA adds the blocks. Every sum is float32 on the vector unit (no
matmul anywhere), whatever dtype u arrives in.

A sum over the lanes a token is what a token's work cannot afford: dB_t
and dC_t are first summed over the block's lane tiles into [N, 128] rows
of scratch (token, state), and once a block each state's TIME rows are
read with a stride, transposed and summed over sublanes.

Sizes, module constants (a sweep edits them), measured on a v5e at the
Phi cell's [4, 4096, 5120], N = 16, u bfloat16, ms a layer (the XLA path
there: F 12.9, F + B 42.2):

* forward, by block of channels: 256 8.0, 512 5.8, 1,024 4.7-5.1,
  **2,560 4.8**, 5,120 6.0;
* backward alone, with a sum over lanes a token, by block of channels:
  128 ~62, 256 ~35, 512 19.5, 1,024 13.6; with the sums a block: 512
  14.5 (summed over lanes by a matmul against ones instead, three
  bfloat16 products for float32's precision: 14.9), **1,024 12.0**. Left
  out at 512, to see what costs: dB and dC 12.2, and the sums over the
  states besides 10.2; the decay kept from the block's forward instead of
  computed again 14.1, for 4 MB more VMEM;
* ``TIME`` 128 or 256 alike, to 1 %: 128 keeps a quarter of the XLA
  path's states, and 256 would halve the backward's block of channels;
* the tokens of a group, forward / backward ms, and the host's seconds
  to trace and lower both kernels: written out 4.79 / 11.79, 0.34 s;
  **unrolled** ``fori_loop`` 4.83 / 12.42, 0.39 s; a ``fori_loop`` not
  unrolled (B, C staged [GROUP, N, 1], Delta, Delta u read at a dynamic
  row) 7.05 / 24.17, 0.15 s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "tl_mamba_scan"  # the gate's name for both kernels
FWD, BWD = NAME + "_fwd", NAME + "_bwd"
STATES = 16  # N: two float32 sublane tiles
LANE = 128
TIME = 128  # tokens a grid step, and the interval of the kept states
GROUP = 16  # tokens a loop iteration: one bfloat16 tile of rows
CHANNELS_FWD = 2560  # at most, a grid step's block of channels
CHANNELS_BWD = 1024
VMEM = 32 * 2**20
F32 = jnp.float32


def _channels(d: int, most: int) -> int:
    """The widest block of whole lane tiles, at most ``most``, that
    divides d."""
    return max(c for c in range(LANE, most + 1, LANE) if d % c == 0)


def _stage(u_ref, dt_ref, b_ref, c_ref, g, dt_s, x_s, b_s, c_s):
    """Group g of a grid step's tokens into VMEM: Delta and Delta u
    [GROUP, ch], B and C as columns [N, GROUP], all float32. Returns the
    group's rows and u [GROUP, ch] float32."""
    rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
    dt, u = dt_ref[0, rows], u_ref[0, rows].astype(F32)
    dt_s[...] = dt
    x_s[...] = dt * u
    b_s[...] = b_ref[0, rows].astype(F32).T
    c_s[...] = c_ref[0, rows].astype(F32).T
    return rows, u


def _advance(At, dt_s, x_s, b_s, i, s):
    """The state after the staged group's token i."""
    at = pl.ds(i, 1)
    return jnp.exp(dt_s[at] * At) * s + x_s[at] * b_s[:, at]


def _tokens(body, carry):
    """body over a group's tokens: traced once, and lowered once a token
    with its index a constant (the loop is unrolled whole)."""
    return jax.lax.fori_loop(0, GROUP, body, carry, unroll=True)


def _fwd_kernel(
    u_ref, dt_ref, b_ref, c_ref, at_ref, d_ref,  # in
    y_ref, h_ref,  # out
    s_ref, dt_s, x_s, b_s, c_s, y32_ref,  # scratch
):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    h_ref[0, 0] = s_ref[...]
    At, D = at_ref[...], d_ref[...]

    def token(i, s):
        s = _advance(At, dt_s, x_s, b_s, i, s)
        y32_ref[pl.ds(i, 1)] = jnp.sum(s * c_s[:, pl.ds(i, 1)], 0, keepdims=True)
        return s

    def group(g, s):
        rows, u = _stage(u_ref, dt_ref, b_ref, c_ref, g, dt_s, x_s, b_s, c_s)
        s = _tokens(token, s)
        y_ref[0, rows] = (y32_ref[...] + D * u).astype(y_ref.dtype)
        return s

    s_ref[...] = jax.lax.fori_loop(0, TIME // GROUP, group, s_ref[...])


def _lane_tiles(p):
    """[N, ch] -> [N, LANE]: the sum of its lane tiles."""
    acc = p[:, :LANE]
    for j in range(LANE, p.shape[1], LANE):
        acc = acc + p[:, j:j + LANE]
    return acc


def _over_lanes(ref):
    """ref [TIME * N, LANE], rows (token, state) -> [N, TIME]: each row's
    sum over its lanes, a state's TIME rows at a time (a strided load),
    transposed and summed over sublanes."""
    return jnp.concatenate([
        jnp.sum(ref[pl.ds(n, TIME, stride=STATES)].T, 0, keepdims=True)
        for n in range(STATES)
    ])


def _bwd_kernel(
    u_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, h_ref, dy_ref,  # in
    du_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,  # out
    st_ref, dsf_ref, da_acc, dd_acc, dt_s, x_s, b_s, c_s, dy_s, dx_rows,
    dg_rows, rb_ref, rc_ref,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        dsf_ref[...] = jnp.zeros_like(dsf_ref)
        da_acc[...] = jnp.zeros_like(da_acc)
        dd_acc[...] = jnp.zeros_like(dd_acc)

    At, D = at_ref[...], d_ref[...]
    steps = TIME // GROUP
    # st_ref[t + 1]: the state after the block's token t; st_ref[0] the
    # state entering the block
    st_ref[0] = h_ref[0, 0]

    def forward(g, s):
        _stage(u_ref, dt_ref, b_ref, c_ref, g, dt_s, x_s, b_s, c_s)

        def token(i, s):
            s = _advance(At, dt_s, x_s, b_s, i, s)
            st_ref[g * GROUP + i + 1] = s
            return s

        return _tokens(token, s)

    jax.lax.fori_loop(0, steps, forward, st_ref[0])

    def backward(j, carry):
        g = steps - 1 - j
        rows, u = _stage(u_ref, dt_ref, b_ref, c_ref, g, dt_s, x_s, b_s, c_s)
        dy = dy_ref[0, rows].astype(F32)
        dy_s[...] = dy

        def token(r, carry):
            dsf, da = carry
            i = GROUP - 1 - r
            t = g * GROUP + i
            at = pl.ds(i, 1)
            dyi, dti = dy_s[at], dt_s[at]
            ds = dsf + c_s[:, at] * dyi
            # dC_t and dB_t, summed over the lane tiles here and over the
            # lanes once a block (the module's docstring)
            sums = pl.ds(pl.multiple_of(t * STATES, STATES), STATES)
            rc_ref[sums] = _lane_tiles(st_ref[t + 1] * dyi)
            rb_ref[sums] = _lane_tiles(ds * x_s[at])
            dx_rows[at] = jnp.sum(ds * b_s[:, at], 0, keepdims=True)
            a = jnp.exp(dti * At)
            q = ds * st_ref[t] * a
            dg_rows[at] = jnp.sum(q * At, 0, keepdims=True)
            return a * ds, da + q * dti

        carry = _tokens(token, carry)
        dx = dx_rows[...]
        du_ref[0, rows] = (dx * dt_s[...] + D * dy).astype(du_ref.dtype)
        ddt_ref[0, rows] = dx * u + dg_rows[...]
        dd_acc[...] += dy * u
        return carry

    dsf_ref[...], da = jax.lax.fori_loop(
        0, steps, backward, (dsf_ref[...], da_acc[...])
    )
    da_acc[...] = da
    db_ref[0, 0], dc_ref[0, 0] = _over_lanes(rb_ref), _over_lanes(rc_ref)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        da_ref[0] = da
        dd_ref[0] = jnp.sum(dd_acc[...], 0, keepdims=True)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        # the backward's block of states is 8.5 MB at 1,024 channels, and
        # a float32 u's buffers take it past the 16 MiB a kernel gets by
        # default (of a v5e core's 128 MiB)
        vmem_limit_bytes=VMEM,
    )


def _staged(N, ch):
    """The scratch ``_stage`` fills: Delta, Delta u, B, C."""
    return [pltpu.VMEM((GROUP, ch), F32)] * 2 + [pltpu.VMEM((N, GROUP), F32)] * 2


def _specs(T, N, ch, back):
    """BlockSpecs of u and Delta (and every [B, T, d] array), of B and C,
    of A^T and of D, their time blocks in reverse where ``back``."""
    nT = T // TIME

    def block(k):
        return nT - 1 - k if back else k

    return (
        pl.BlockSpec((1, TIME, ch), lambda b, j, k: (b, block(k), j)),
        pl.BlockSpec((1, TIME, N), lambda b, j, k: (b, block(k), 0)),
        pl.BlockSpec((N, ch), lambda b, j, k: (0, j)),
        pl.BlockSpec((1, ch), lambda b, j, k: (0, j)),
        pl.BlockSpec((1, 1, N, ch), lambda b, j, k: (b, block(k), 0, j)),
    )


def _check(u, At):
    B, T, d = u.shape
    if At.shape[0] != STATES or d % LANE or T % TIME:
        raise ValueError(
            f"{NAME}: u {u.shape}, A^T {At.shape} is not {STATES} states of "
            f"lane-tiled channels over whole blocks of {TIME} tokens"
        )


@functools.partial(jax.jit, static_argnames="interpret")
def scan_fwd(u, dt, At, Bm, Cm, D, *, interpret: bool = False):
    """u, dt [B,T,d] (u bfloat16 or float32, dt float32); At [N,d]; Bm,
    Cm [B,T,N]; D [1,d] float32 -> (y [B,T,d] in u's dtype, the state
    entering each block of ``TIME`` tokens [B, T / TIME, N, d] float32).
    Jitted so that a model's layers share one trace and one lowering."""
    _check(u, At)
    B, T, d = u.shape
    N, ch = At.shape[0], _channels(d, CHANNELS_FWD)
    rows, bc, at, dd, state = _specs(T, N, ch, back=False)
    return pl.pallas_call(
        _fwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((B, T // TIME, N, d), F32),
        ),
        grid=(B, d // ch, T // TIME),
        in_specs=[rows, rows, bc, bc, at, dd],
        out_specs=(rows, state),
        scratch_shapes=[
            pltpu.VMEM((N, ch), F32),  # the state
            *_staged(N, ch),
            pltpu.VMEM((GROUP, ch), F32),  # y, a group's rows
        ],
        compiler_params=_params(),
        interpret=interpret,
        name=FWD,
    )(u, dt, Bm, Cm, At, D)


@functools.partial(jax.jit, static_argnames="interpret")
def scan_bwd(u, dt, At, Bm, Cm, D, h, dy, *, interpret: bool = False):
    """The cotangents of ``scan_fwd``'s y for its six operands, h its
    kept states and dy y's cotangent: (du in u's dtype, dDelta [B,T,d],
    dA^T [N,d], dB, dC [B,T,N], dD [1,d]), all but du float32."""
    _check(u, At)
    B, T, d = u.shape
    N, ch = At.shape[0], _channels(d, CHANNELS_BWD)
    nc = d // ch
    rows, bc, at, dd, state = _specs(T, N, ch, back=True)
    nT = T // TIME
    sums = pl.BlockSpec((1, 1, N, TIME), lambda b, j, k: (b, j, 0, nT - 1 - k))
    du, ddt, db, dc, da, dD = pl.pallas_call(
        _bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct(u.shape, F32),
            jax.ShapeDtypeStruct((B, nc, N, T), F32),
            jax.ShapeDtypeStruct((B, nc, N, T), F32),
            jax.ShapeDtypeStruct((B, N, d), F32),
            jax.ShapeDtypeStruct((B, 1, d), F32),
        ),
        grid=(B, nc, nT),
        in_specs=[rows, rows, bc, bc, at, dd, state, rows],
        out_specs=(
            rows, rows, sums, sums,
            pl.BlockSpec((1, N, ch), lambda b, j, k: (b, 0, j)),
            pl.BlockSpec((1, 1, ch), lambda b, j, k: (b, 0, j)),
        ),
        scratch_shapes=[
            pltpu.VMEM((TIME + 1, N, ch), F32),  # the block's states
            pltpu.VMEM((N, ch), F32),  # ds's decayed part, into the block
            pltpu.VMEM((N, ch), F32),  # dA
            pltpu.VMEM((GROUP, ch), F32),  # dD, by row of a group
            *_staged(N, ch),
            pltpu.VMEM((GROUP, ch), F32),  # dy, a group's rows
            pltpu.VMEM((GROUP, ch), F32),  # dx, a group's rows
            pltpu.VMEM((GROUP, ch), F32),  # dDelta through the decay
            pltpu.VMEM((TIME * N, LANE), F32),  # dB, by (token, state)
            pltpu.VMEM((TIME * N, LANE), F32),  # dC, the same
        ],
        compiler_params=_params(),
        interpret=interpret,
        name=BWD,
    )(u, dt, Bm, Cm, At, D, h, dy)
    return (
        du, ddt, da.sum(0), db.sum(1).swapaxes(1, 2),
        dc.sum(1).swapaxes(1, 2), dD.sum(0),
    )
