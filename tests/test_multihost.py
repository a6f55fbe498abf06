"""Multi-HOST mesh formation (SURVEY §2.4/§5.8, VERDICT r3 missing #1).

Two OS processes, each with 4 virtual CPU devices, join one JAX runtime
via jax.distributed (gRPC coordination, the CPU stand-in for a TPU pod
slice's DCN) and run the SAME GPT-2 ShardedTrainer program over one
GLOBAL {data:2, pipe:2, model:2} mesh — the data axis spans processes.
The loss trajectory must be identical to the single-process 8-device
run of the same workload (tests/multihost_worker.py holds the body).
"""

import json
import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _single_process_reference(devices):
    """Same workload on this process's own 8-device mesh."""
    from tensorlink_tpu.config import MeshConfig, TrainConfig
    from tensorlink_tpu.models.gpt2 import GPT2, GPT2Config
    from tensorlink_tpu.parallel.engine import ShardedTrainer
    from tensorlink_tpu.runtime.mesh import make_mesh
    from tensorlink_tpu.train.trainer import softmax_cross_entropy

    mesh = make_mesh(MeshConfig(data=2, pipe=2, model=2))
    model = GPT2(GPT2Config(
        vocab_size=128, dim=32, num_layers=4, num_heads=2, max_len=64,
        dropout=0.0,
    ))
    params = model.init(jax.random.key(0))
    parts = model.as_pipeline_parts(params)
    cfg = TrainConfig(
        batch_size=8, micro_batches=4, learning_rate=0.01,
        optimizer="sgd", grad_clip_norm=None, dtype="float32",
    )
    tr = ShardedTrainer(mesh, cfg, parts, lambda lg, b: softmax_cross_entropy(
        lg, b["labels"]))
    state = tr.init_state()
    from tensorlink_tpu.data import ShardedLoader

    r = np.random.default_rng(0)
    ids = r.integers(0, 128, (16, 17))
    ds = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    loader = ShardedLoader(ds, global_batch=8, seed=0,
                           process_index=0, process_count=1)
    losses = []
    for batch in loader.epochs(1):
        state, m = tr.train_step(state, batch)
        losses.append(float(m["loss"]))
    return losses


def test_two_process_mesh_matches_single_process(devices):
    coord = f"127.0.0.1:{_free_port()}"
    # the children are CPU-only and their environment says so: 4
    # virtual devices each, 8 in the global mesh
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, coord, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            cwd=os.path.dirname(os.path.dirname(_WORKER)),
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host worker timed out")
        assert p.returncode == 0, (
            f"worker failed: {err.decode(errors='replace')[-800:]}"
        )
        outs.append(json.loads(out.decode().strip().splitlines()[-1]))

    ref = _single_process_reference(devices)
    for o in outs:
        # SPMD determinism: bitwise-identical program on identical data —
        # the multi-host trajectory must equal the single-process one
        np.testing.assert_allclose(o["losses"], ref, rtol=1e-6)
    assert outs[0]["losses"] == outs[1]["losses"]
