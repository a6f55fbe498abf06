"""MoE capacity-factor sweep: router drop fraction vs training quality.

VERDICT r4 weak #4: the bench shipped a 14.5% token-drop fraction as a
telemetry field with no evidence of what dropping does to loss. This
experiment trains the SAME tiny MoE LM (same init, same data order) at
capacity_factor 1.0 / 1.25 / 2.0 and a dropless control (capacity >=
top_k * tokens, so nothing can overflow), and records final train loss,
eval loss, and the measured drop fraction. Quality impact is a property
of the routing algebra, not the accelerator, so the sweep runs anywhere.
Run: python examples/moe_capacity_sweep.py [steps]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def run(steps: int = 200) -> dict:
    import dataclasses

    from tensorlink_tpu.models.llama import Llama, LlamaConfig
    from tensorlink_tpu.train.optim import apply_updates, make_optimizer
    from tensorlink_tpu.train.trainer import TrainState, softmax_cross_entropy

    base = LlamaConfig(
        vocab_size=512, dim=64, num_layers=2, num_heads=4, num_kv_heads=4,
        hidden_dim=128, max_len=128, moe_experts=8, moe_top_k=2,
    )
    B, T = 16, 64
    r = np.random.default_rng(0)
    # structured synthetic LM data (repeated motifs) so loss can actually
    # fall below the uniform floor and capacity pressure matters
    motifs = r.integers(0, base.vocab_size, (8, 16))

    def batch_at(step, rng):
        rows = []
        for _ in range(B):
            seq = np.concatenate(
                [motifs[rng.integers(0, len(motifs))] for _ in range(T // 16 + 1)]
            )[: T + 1]
            rows.append(seq)
        a = np.stack(rows)
        return {
            "input_ids": jnp.asarray(a[:, :-1]),
            "labels": jnp.asarray(a[:, 1:]),
        }

    results = {}
    # dropless control: capacity_factor big enough that C >= top_k * T
    for label, cf in (("1.0", 1.0), ("1.25", 1.25), ("2.0", 2.0),
                      ("dropless", float(base.moe_experts * base.moe_top_k))):
        cfg = dataclasses.replace(base, moe_capacity_factor=cf)
        model = Llama(cfg)
        params = model.init(jax.random.key(0))
        opt = make_optimizer("adam", 1e-3)
        state = TrainState.create(params, opt)

        def loss_fn(p, b):
            logits, aux = model.apply_with_aux(p, b["input_ids"])
            return softmax_cross_entropy(logits, b["labels"]) + 0.01 * aux

        @jax.jit
        def step_fn(st, b):
            loss, grads = jax.value_and_grad(loss_fn)(st.params, b)
            upd, os_ = opt.update(grads, st.opt_state, st.params, st.step)
            return TrainState(
                params=apply_updates(st.params, upd), opt_state=os_,
                step=st.step + 1,
            ), loss

        rng = np.random.default_rng(1)  # same data order for every cf
        losses = []
        for i in range(steps):
            state, loss = step_fn(state, batch_at(i, rng))
            losses.append(float(loss))
        eval_b = batch_at(0, np.random.default_rng(2))
        eval_loss = float(loss_fn(state.params, eval_b))
        # drop fraction on what layer-0's router sees after training —
        # via the block's own wiring (TransformerBlock.routing_stats)
        blk = model.children["blocks"].children["0"]
        emb = model.children["tok_emb"].apply(
            state.params["tok_emb"], eval_b["input_ids"]
        )
        stats = blk.routing_stats(state.params["blocks"]["0"], emb)
        results[label] = {
            "capacity_factor": cf,
            "final_train_loss": round(float(np.mean(losses[-10:])), 4),
            "eval_loss": round(eval_loss, 4),
            "drop_fraction": round(float(stats["drop_fraction"]), 4),
        }
        print(label, results[label], flush=True)
    return results


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200
    out = run(n)
    import json

    print(json.dumps(out))
