"""Mamba-1's selective scan (Gu & Dao 2023, "Mamba: linear-time sequence
modeling with selective state spaces") in chunks, in plain XLA, and both
passes as Pallas kernels where a gate allows it (the last paragraph).

A channel's state s [N] follows, token by token (the definition;
``benchmark/reference/phi4flash.py::selective_scan`` runs it as written
and the tests hold this file to it),

    s_t = exp(Delta_t A) * s_{t-1} + (Delta_t u_t) B_t      s_0 = 0
    y_t = s_t . C_t + D u_t

with the step Delta_t per token and channel, A [d_inner, N] negative,
B_t and C_t [N] per token and shared by the channels. The decay is per
channel and per state, so nothing here factors into matmuls (as a decay
that is a scalar a head would): the state [B, d_inner, N] moves by
elementwise work, and [B, T, d_inner, N] (5.4 GB in float32 at 16,384
tokens of 5,120 channels) never exists, forward or backward.

Time goes in chunks of ``CHUNK`` tokens under a ``lax.scan`` that
carries the state in float32, laid out [B, N, d_inner] (the channels
along the lanes); inside a chunk the tokens follow one another, written
out (an unrolled scan), so that the compiler makes one fusion of a chunk
that reads and writes the state once. What is kept between the passes
is the state that enters each chunk, [T / CHUNK, B, N, d_inner] (335 MB
at that shape): the backward pass (a ``jax.custom_vjp``) goes over the
chunks in reverse and, for each, recomputes its states from the one
carried into it and takes ``jax.vjp`` of the chunk alone. So a layer
runs the forward F once in the step and once more, chunk by chunk, with
the backward B. Measured on a v5e at [4, 4096, 5120], N = 16, F and F +
B of one layer (PR 36): chunks of 64 with the tokens unrolled by 8 take
11.9 + 77.9 ms, by 4 11.1 + 65.2; chunks of 32 by 4 57.7 in all; chunks
of 16 written out 35.1, of 8 38.5: a chunk written out whole wins, and
16 beats 8 with half the kept states.

The result and the kept states carry the names in ``KEPT``
(``checkpoint_name``), identities unless an enclosing ``jax.checkpoint``
has a policy that saves them: under such a one
(``models/phi4flash.py``'s block remat) the block's recompute holds no
pass of the scan (PR 30's mechanism, as ``ops/kda.py::KEPT``).

Delta, the decay, the state and the sums are float32 whatever dtype u
arrives in; y leaves in u's dtype.

Which pass runs where. On a TPU, for channels in whole 128-lane tiles,
16 states, Delta float32 and u, B, C bfloat16 or float32
(``_kernel_path``), the scan is the two kernels of
``ops/pallas/selective_scan.py`` behind a ``jax.custom_vjp``: the step's
forward is ``tl_mamba_scan_fwd``, which reads u, Delta, B, C as they
come ([B, T, ·], no chunked copies) and writes y and the state entering
each block of ``TIME`` = 128 tokens, [B, T / 128, N, d] (the kept
states, by the same name); the backward is ``tl_mamba_scan_bwd``, which
runs each block forward again in VMEM and back. Under the model's block
remat a layer so runs each kernel once. A length that is no whole number
of blocks is padded as above. Everywhere else (the CPU, a mesh XLA
partitions, other widths or dtypes) it is this file's XLA program, and
on a TPU the closed gate says why (``kernel.gate_closed``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tensorlink_tpu.ops.pallas import gate_closed, on_tpu, partitioned_by_xla
from tensorlink_tpu.ops.pallas import selective_scan as kernel

CHUNK = 16
# the names the result and the chunks' entry states carry, for a remat
# policy to save
KEPT = ("mamba_scan_y", "mamba_scan_states")


def _chunk(s, xs, At):
    """One chunk, token after token. s [B,N,d]; xs = (dt [C,B,d], du
    [C,B,d], b [C,B,N], c [C,B,N]); At [N,d] -> (s, y [C,B,d])."""

    def token(s, x):
        dt, du, b, c = x
        s = jnp.exp(dt[:, None, :] * At) * s + du[:, None, :] * b[:, :, None]
        return s, jnp.sum(s * c[:, :, None], 1)

    return jax.lax.scan(token, s, xs, unroll=True)


@jax.custom_vjp
def _scan(xs, At):
    """xs as ``_chunk`` takes them with a leading axis of chunks
    [nc, C, B, ...] -> y [nc, C, B, d]."""
    return _scan_fwd(xs, At)[0]


def _scan_fwd(xs, At):
    B, N, d = xs[0].shape[2], xs[2].shape[3], xs[0].shape[3]

    def step(s, x):
        s_out, y = _chunk(s, x, At)
        return s_out, (y, s)

    _, (y, entered) = jax.lax.scan(
        step, jnp.zeros((B, N, d), jnp.float32), xs
    )
    return y, (xs, At, checkpoint_name(entered, KEPT[1]))


def _scan_bwd(res, dy):
    xs, At, entered = res

    def step(carry, x):
        ds, dA = carry
        s_in, x_c, dy_c = x
        _, vjp = jax.vjp(_chunk, s_in, x_c, At)
        ds_in, dx_c, dA_c = vjp((ds, dy_c))
        return (ds_in, dA + dA_c), dx_c

    (_, dA), dxs = jax.lax.scan(
        step, (jnp.zeros_like(entered[0]), jnp.zeros_like(At)),
        (entered, xs, dy), reverse=True,
    )
    return dxs, dA


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, A, Bm, Cm, D, *, interpret: bool = False):
    """u, delta [B,T,d]; A [d,N] (negative); Bm, Cm [B,T,N]; D [d] ->
    y [B,T,d] in u's dtype. A length that is no whole number of chunks
    (of the kernels' time blocks) is padded at its end with tokens whose
    step is 0 (they leave the state as it is and write nothing) and whose
    outputs are cut off. ``interpret`` runs the kernel path off the TPU,
    interpreted."""
    B, T, d = u.shape
    f32 = jnp.float32
    if _kernel_path(u, delta, Bm, Cm, interpret):
        pad = ((0, 0), (0, -T % kernel.TIME), (0, 0))
        y = _by_kernel(
            interpret, *(jnp.pad(x, pad) for x in (u, delta)),
            A.astype(f32).T, *(jnp.pad(x, pad) for x in (Bm, Cm)),
            D.astype(f32)[None],
        )
        return checkpoint_name(y[:, :T], KEPT[0])
    C = min(CHUNK, T)
    pad = -T % C
    uf, dt = u.astype(f32), delta.astype(f32)

    def by_chunk(x):  # [B,T,k] -> [nc, C, B, k]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(x, 1, 0).reshape((T + pad) // C, C, B, -1)

    xs = tuple(map(by_chunk, (dt, dt * uf, Bm, Cm)))
    y = _scan(xs, A.astype(f32).T)
    y = jnp.moveaxis(y.reshape(T + pad, B, d), 0, 1)[:, :T]
    y = y + D.astype(f32) * uf
    return checkpoint_name(y.astype(u.dtype), KEPT[0])


def _kernel_path(u, delta, Bm, Cm, interpret) -> bool:
    """Static gate for ``tl_mamba_scan_fwd`` and ``_bwd``: silent off the
    TPU (the XLA path is the only one there); on it a refusal records
    its reason."""
    if not interpret and not on_tpu():
        return False
    closed = functools.partial(
        gate_closed, kernel.NAME, u=u.shape, states=Bm.shape[-1]
    )
    if not interpret and (why := partitioned_by_xla()):
        return closed(why)
    if u.shape[-1] % kernel.LANE:
        return closed(
            f"{u.shape[-1]} channels: no whole number of {kernel.LANE}-lane tiles"
        )
    if Bm.shape[-1] != kernel.STATES:
        return closed(f"{Bm.shape[-1]} states a channel, not {kernel.STATES}")
    two = ("bfloat16", "float32")
    dtypes = [x.dtype.name for x in (u, delta, Bm, Cm)]
    if dtypes[1] != "float32" or not all(t in two for t in dtypes):
        return closed(
            f"operands {dtypes}: delta not float32, or u, B, C not "
            "bfloat16 or float32"
        )
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _by_kernel(interpret, u, dt, At, Bm, Cm, D):
    return kernel.scan_fwd(u, dt, At, Bm, Cm, D, interpret=interpret)[0]


def _by_kernel_fwd(interpret, *xs):
    y, entered = kernel.scan_fwd(*xs, interpret=interpret)
    return y, (xs, checkpoint_name(entered, KEPT[1]))


def _by_kernel_bwd(interpret, res, dy):
    xs, entered = res
    grads = kernel.scan_bwd(*xs, entered, dy, interpret=interpret)
    return tuple(g.astype(x.dtype) for g, x in zip(grads, xs))


_by_kernel.defvjp(_by_kernel_fwd, _by_kernel_bwd)
