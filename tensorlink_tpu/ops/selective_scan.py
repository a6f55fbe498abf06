"""Mamba-1's selective scan (Gu & Dao 2023, "Mamba: linear-time sequence
modeling with selective state spaces") in chunks, in plain XLA.

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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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


def selective_scan(u, delta, A, Bm, Cm, D):
    """u, delta [B,T,d]; A [d,N] (negative); Bm, Cm [B,T,N]; D [d] ->
    y [B,T,d] in u's dtype. A length that is no whole number of chunks
    is padded at its end with tokens whose step is 0 (they leave the
    state as it is and write nothing) and whose outputs are cut off."""
    B, T, d = u.shape
    f32 = jnp.float32
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
