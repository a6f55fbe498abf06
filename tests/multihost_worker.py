"""Subprocess body for test_multihost.py: one process of a 2-process
multi-HOST mesh (jax.distributed over gRPC/Gloo on localhost, 4 virtual
CPU devices per process -> 8 global).

Runs the same GPT-2 engine parity workload as the single-process tests
over a {data:2, pipe:2, model:2} GLOBAL mesh and prints the loss
trajectory as one JSON line. Not a pytest file — invoked as
``python multihost_worker.py <coordinator> <process_id>`` with the CPU
platform and its 4 virtual devices named in the environment
(``JAX_PLATFORMS``, ``XLA_FLAGS``), as test_multihost.py passes them.
"""

import json
import os
import sys

# script execution puts tests/ (not the repo root) on sys.path, and the
# venv has no installed tensorlink_tpu — the parent pytest process gets
# the root from its rootdir, but this subprocess must pin it itself
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    coordinator, pid = sys.argv[1], int(sys.argv[2])
    import jax
    import numpy as np

    from tensorlink_tpu.config import DistributedConfig, MeshConfig, TrainConfig
    from tensorlink_tpu.models.gpt2 import GPT2, GPT2Config
    from tensorlink_tpu.parallel.engine import ShardedTrainer
    from tensorlink_tpu.runtime.mesh import initialize_distributed, make_mesh
    from tensorlink_tpu.train.trainer import softmax_cross_entropy

    info = initialize_distributed(DistributedConfig(
        coordinator=coordinator, num_processes=2, process_id=pid
    ))
    assert info["global_devices"] == 8, info
    assert info["local_devices"] == 4, info

    mesh = make_mesh(MeshConfig(data=2, pipe=2, model=2))
    model = GPT2(GPT2Config(
        vocab_size=128, dim=32, num_layers=4, num_heads=2, max_len=64,
        dropout=0.0,
    ))
    # identical seeds on every process -> identical params/batch; the
    # engine's device_put scatters each process's addressable shards
    params = model.init(jax.random.key(0))
    parts = model.as_pipeline_parts(params)
    cfg = TrainConfig(
        batch_size=8, micro_batches=4, learning_rate=0.01,
        optimizer="sgd", grad_clip_norm=None, dtype="float32",
    )
    tr = ShardedTrainer(mesh, cfg, parts, lambda lg, b: softmax_cross_entropy(
        lg, b["labels"]))
    state = tr.init_state()
    # the data pipeline is multi-host too: each process's ShardedLoader
    # yields only ITS rows of the global batch, and prefetch_to_device
    # assembles the global array from process-local shards — no host
    # ever holds another host's data
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorlink_tpu.data import ShardedLoader, prefetch_to_device

    r = np.random.default_rng(0)
    ids = r.integers(0, 128, (16, 17))
    ds = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    loader = ShardedLoader(ds, global_batch=8, seed=0)  # process-aware
    sh = NamedSharding(mesh, P(("data",)))
    losses = []
    for batch in prefetch_to_device(loader.epochs(1), sh):
        state, m = tr.train_step(state, batch)
        losses.append(float(m["loss"]))
    print(json.dumps({"process": pid, "losses": losses}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
