"""The routed experts' selection bias, set from the load and not from
the seed.

A router's columns are made from ``--seed`` like every matrix
(``weights.py``). Hidden states under random weights share a common
part, so such a router favours some experts, and which ones is the
seed's: the rows a chip's held experts get, and with them the step's
time, would follow the seed (PERF.md section 6, PR 34). No deployment
is in that state. The published recipe (``topk_method: noaux_tc``)
keeps the load even by a bias that is added to the scores for the
choice only, gets no gradient, and is moved by the load it sees:

    b_e <- b_e + u * sign(mean load - load_e)

A trained model's bias is that rule's fixed point. So for a family
with routed experts (one whose file has ``router_scores``) the
benchmark runs the rule to its fixed point on the run's first batch,
from b = 0 with u shrinking, layer after layer in one forward pass,
since a layer's bias changes what the layers after it see. What comes
out is numbers, a few floats an expert layer, rounded to the step's
compute dtype so that the program's cast loses nothing: the driver
lays the same ones over the program's tree, the reference's and the
start that changes are taken from. There is no switch; a family with
no ``router_scores`` has no routed experts and nothing here runs for
it.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import traffic, weights
from benchmark.harness import note

ROUNDS = 160  # of the rule, a layer
U_FIRST, U_LAST = 0.03, 1e-5  # u, shrinking by the same factor a round


@functools.partial(jax.jit, static_argnames="k")
def loads(scores, bias, k: int):
    """[E] tokens that choose each expert: those among a token's ``k``
    largest of ``scores + bias``."""
    pick = scores + bias
    kth = jax.lax.top_k(pick, k)[0][:, -1:]
    return jnp.sum(pick >= kth, 0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames="k")
def solve(scores, k: int):
    """Scores [N, E] (float32, a token a row) -> the bias [E] (float32,
    mean zero) under which the counts of ``top_k(scores + bias, k)``
    are even: the published rule from b = 0, ``ROUNDS`` rounds, u from
    ``U_FIRST`` down to ``U_LAST``. Deterministic."""
    n, e = scores.shape
    shrink = (U_LAST / U_FIRST) ** (1.0 / (ROUNDS - 1))

    def one_round(i, b):
        u = U_FIRST * shrink ** i.astype(jnp.float32)
        return b + u * jnp.sign(n * k / e - loads(scores, b, k))

    b = jax.lax.fori_loop(0, ROUNDS, one_round, jnp.zeros((e,), jnp.float32))
    return b - jnp.mean(b)  # the choice takes no notice of a common shift


def paths(tree) -> list[str]:
    """The selection bias leaves of a tree (``.../router/bias``), in the
    layers' order."""
    found = [
        weights.path_str(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]
    return sorted(
        (p for p in found if p.endswith("router/bias")),
        key=lambda p: [int(s) if s.isdigit() else s for s in p.split("/")],
    )


def lay_over(tree, biases: dict):
    """``tree`` with each leaf that ``biases`` names (by path) replaced
    by its numbers; the other leaves are the tree's own."""
    if not biases:
        return tree

    def leaf(path, x):
        new = biases.get(weights.path_str(path))
        return x if new is None else jnp.asarray(new, x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, tree)


def as_the_step_casts(params, compute_dtype):
    """``Trainer._loss_for_grad``'s cast of the float32 masters."""
    return jax.tree.map(
        lambda x: x.astype(compute_dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )


def run(family, model, params, cfg: dict, mix: dict, seed: int) -> dict:
    """The selection bias of every expert layer of ``params`` (the tree
    from the seed, float32), by path, as host arrays: one jitted forward
    pass over batch 0 through the family's ``router_scores`` in the
    step's compute dtype, no gradient; at each expert layer the rule is
    run on the scores that layer's router sees, and the layer's experts
    then choose under what it found, so that the next layer's scores are
    the ones the program will see. Prints the ``balance`` note."""
    t0 = time.perf_counter()
    routed = family.routed_experts(cfg)
    k, (first, held) = routed["k"], routed["held"]
    dtype = jnp.dtype(cfg["train"]["compute_dtype"])
    ids = traffic.train_batch(mix, cfg["vocab_size"], seed, 0)[:, :-1]

    @jax.jit
    def sweep(params, ids):
        found = []

        def settle(scores):
            b = solve(scores, k).astype(dtype).astype(jnp.float32)
            found.append((b, loads(scores, b, k)))
            return b

        family.router_scores(
            model, as_the_step_casts(params, dtype), ids, settle
        )
        return found

    found = [
        (np.asarray(b), np.asarray(n))
        for b, n in sweep(params, jnp.asarray(ids))
    ]
    names = paths(params)
    if len(names) != len(found):
        raise ValueError(
            f"{len(found)} expert layers gave scores, the tree has "
            f"{len(names)} selection biases"
        )
    note(
        phase="balance", layers=names, rounds=ROUNDS, u=[U_FIRST, U_LAST],
        mean_load=ids.size * k / found[0][1].size,
        expert_least=[int(n.min()) for _, n in found],
        expert_most=[int(n.max()) for _, n in found],
        held_routes=[int(n[first:first + held].sum()) for _, n in found],
        seconds=round(time.perf_counter() - t0, 3),
    )
    return {path: b for path, (b, _) in zip(names, found)}
