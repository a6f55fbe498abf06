"""chip_smoke.py rehearsed on the CPU, on every PR.

The script itself refuses to run without a TPU; its phase FUNCTIONS
take the model configuration as an argument, so the paths, arguments
and comparisons of a chip run are exercised here at tiny sizes (what
only a chip can show — which kernels a compiled program holds — is
checked in ``chip_smoke.main``). Plus the two rules for where the
compile cache lives.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_refuses_without_a_tpu():
    """As the driver runs it, in a sandbox with no accelerator: stops
    at the device phase, non-zero, and no ``ok: true`` line."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),  # a CPU-only child
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert '"phase": "device"' in out.stdout
    assert "need 1 tpu device" in out.stderr


def test_a_failed_phase_ends_the_run(monkeypatch, capsys):
    """A phase whose check does not hold raises out of ``main`` (the
    process then exits non-zero) after printing its own line, and the
    result line is never printed."""
    device = {"platform": "tpu", "kind": "rehearsal", "count": 1}
    monkeypatch.setattr(
        chip_smoke, "device_phase",
        lambda n: {**device, "compile_cache_dir": None,
                   "compile_cache_entries": 0},
    )
    monkeypatch.setattr(
        chip_smoke, "serve_phase",
        lambda cfg, **kw: ({"decode_kernels": [], "gates_closed": []},
                           ["made to fail"]),
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="made to fail"):
        chip_smoke.main([])
    out = capsys.readouterr().out
    assert '"phase": "serve", "ok": false' in out
    assert '"ok": true' not in out


def test_serve_phase_tiny():
    from tensorlink_tpu.models.gpt2 import GPT2Config

    facts, failures = chip_smoke.serve_phase(
        GPT2Config.tiny(), max_len=64, requests=3, shared=8, unique=4,
        new_tokens=6, block_size=4,
    )
    assert not failures, failures
    assert facts["tokens"] == 18
    assert facts["prefix_hit_rate"] > 0  # the shared prompt was reused
    assert facts["teacher_forced"]["worst_rank"] < chip_smoke.RANK_TOL
    assert facts["decode_kernels"] == []  # no TPU custom call on a CPU


def test_serve_phase_catches_wrong_tokens(monkeypatch):
    """The comparison bites: tokens the model would not have chosen
    rank far from the top under its own logits."""
    from tensorlink_tpu.models.gpt2 import GPT2Config

    real = chip_smoke._serve_over_sockets

    async def corrupted(engine, prompts, **kw):
        sched, outs, wall, compiles = await real(engine, prompts, **kw)
        return sched, [(o + 17) % 128 for o in outs], wall, compiles

    monkeypatch.setattr(chip_smoke, "_serve_over_sockets", corrupted)
    _, failures = chip_smoke.serve_phase(
        GPT2Config.tiny(), max_len=64, requests=2, shared=8, unique=4,
        new_tokens=6, block_size=4,
    )
    assert failures and "ranks" in failures[0]


def test_serve_kernel_phase_tiny():
    """Llama-shaped tiny config with a window; the kernels run in
    interpret mode against their references."""
    from tensorlink_tpu.models.llama import LlamaConfig

    cfg = dataclasses.replace(LlamaConfig.tiny(), attn_window=16)
    facts, failures = chip_smoke.serve_kernel_phase(
        cfg, max_len=32, requests=2, prompt_len=6, new_tokens=4,
        block_size=4, interpret=True,
    )
    assert not failures, failures
    assert set(facts["kernel_errors"]) == {
        "paged_decode_bf16_T1", "paged_decode_bf16_T4",
        "paged_decode_int8_T1", "paged_decode_int8_T4",
        "fused_residual_norm_rms", "flash_fwd_causal",
    }
    for pools in ("kv_bf16", "kv_int8"):
        assert facts[pools]["tokens"] == 8
        assert facts[pools]["teacher_forced"]["worst_rank"] < 5


def test_train_phase_tiny():
    from tensorlink_tpu.models.bert import BertConfig

    facts, failures = chip_smoke.train_phase(
        BertConfig.tiny(), batch=4, seq=16, steps=3
    )
    assert not failures, failures
    assert len(facts["losses"]) == 3
    assert facts["losses"][-1] < facts["losses"][0]


def test_kda_phase_tiny():
    facts, failures = chip_smoke.kda_phase(
        rows=2, seq=256, heads=2, head_dim=16)
    assert not failures, failures
    assert set(facts["gaps"]) == {"o", "dq", "dk", "dv", "dg", "dbeta"}
    # the slowest channel keeps most of a chunk's state, the fastest none
    fastest, slowest = facts["chunk_log_decay"]
    assert fastest < -20 and slowest > -2
    assert facts["state_carried"] > chip_smoke.KDA_CARRIED


def test_kda_phase_catches_a_lost_state(monkeypatch):
    """A recurrence that forgets its state between chunks (what the
    benchmark's fast-decaying seeded weights would let through) stands
    far off the reference here, in the output and in every gradient."""
    import tensorlink_tpu.ops.kda as ops_kda

    real = ops_kda.kda_chunked

    def forgetful(q, k, v, g, beta):
        B, T = q.shape[:2]
        cut = (
            x.reshape(B * T // ops_kda.CHUNK, ops_kda.CHUNK, *x.shape[2:])
            for x in (q, k, v, g, beta)
        )
        return real(*cut).reshape(B, T, *v.shape[2:])

    monkeypatch.setattr(ops_kda, "kda_chunked", forgetful)
    _, failures = chip_smoke.kda_phase(rows=2, seq=256, heads=2, head_dim=16)
    assert len(failures) >= 6 and "o stands" in failures[0]


def test_mamba_phase_tiny():
    from tensorlink_tpu.runtime.flight import default_recorder

    # gate events are the process's: another file on this worker
    # (test_selective_scan.py closes the gate on purpose) leaves its own
    default_recorder().clear()
    facts, failures = chip_smoke.mamba_phase(
        rows=1, seq=1024, d_inner=32, d_state=16)
    assert not failures, failures
    assert set(facts["gaps"]) == {"y", "du", "ddelta", "dA", "dB", "dC", "dD"}
    # the slowest state keeps most of a chunk, the fastest none of it
    fastest, slowest = facts["chunk_log_decay"]
    assert fastest < -10 and slowest > -1
    assert facts["state_carried"] > chip_smoke.MAMBA_CARRIED
    # off the TPU the kernels' gate is shut, and says nothing
    assert facts["kernels"] == [] and facts["gates_closed"] == []


def test_mamba_phase_catches_a_lost_state(monkeypatch):
    """A scan that forgets its state between chunks (what the
    benchmark's fast-decaying seeded weights would all but let through)
    stands far off the recurrence here, in the output and in every
    gradient."""
    import tensorlink_tpu.ops.selective_scan as ops_scan

    real = ops_scan.selective_scan

    def forgetful(u, delta, A, Bm, Cm, D):
        B, T, E = u.shape
        cut = (
            x.reshape(B * T // ops_scan.CHUNK, ops_scan.CHUNK, x.shape[-1])
            for x in (u, delta, Bm, Cm)
        )
        u, delta, Bm, Cm = cut
        return real(u, delta, A, Bm, Cm, D).reshape(B, T, E)

    monkeypatch.setattr(ops_scan, "selective_scan", forgetful)
    _, failures = chip_smoke.mamba_phase(
        rows=1, seq=1024, d_inner=32, d_state=16)
    assert len(failures) >= 6 and "y stands" in failures[0]


def test_multichip_phase_tiny(devices):
    """The four-chip path on virtual devices: the sharded trainer
    against the plain one, the model=4 engine against the one-device
    one, and parameters on every device of the mesh."""
    from tensorlink_tpu.models.gpt2 import GPT2Config

    facts, failures = chip_smoke.multichip_phase(
        GPT2Config.tiny(), seq=16, batch=8, steps=2, prompts=2,
        prompt_len=8, new_tokens=6,
    )
    assert not failures, failures
    assert facts["infer"]["tokens_equal"]
    for side in ("train", "infer"):
        used = {d for d, n in facts[side]["param_bytes"].items() if n}
        assert used == {0, 1, 2, 3}
    assert facts["train"]["shards"]["stages.attn.q.w"]["devices"] == [
        0, 1, 2, 3
    ]


@pytest.fixture
def fresh_cache_module(monkeypatch):
    """runtime.compile_cache with its process-wide latch open, and
    jax's cache directory put back afterwards."""
    from tensorlink_tpu.runtime import compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(compile_cache, "_active_dir", None)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
    yield compile_cache
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_outside_is_not_set_in_code(
    fresh_cache_module, monkeypatch, tmp_path
):
    cc = fresh_cache_module
    outside = str(tmp_path / "outside")
    monkeypatch.setenv(cc.JAX_ENV_VAR, outside)
    updates = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real(k, v))[1],
    )
    from tensorlink_tpu.runtime.flight import FlightRecorder

    rec = FlightRecorder()
    # a NodeConfig.compile_cache_dir asking for another place loses,
    # on the record
    assert cc.enable_compile_cache(str(tmp_path / "asked"), recorder=rec) == outside
    assert "jax_compilation_cache_dir" not in updates
    assert os.path.isdir(outside)
    assert [e["attrs"]["requested"] for e in rec.events(
        kind="compile_cache.conflict"
    )] == [str(tmp_path / "asked")]


def test_cache_dir_defaults_to_the_checkout(fresh_cache_module, monkeypatch):
    cc = fresh_cache_module
    made = []
    monkeypatch.setattr(
        cc.Path, "mkdir", lambda self, **kw: made.append(str(self))
    )
    assert cc.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    assert cc.enable_compile_cache() == cc.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
    assert made == [cc.DEFAULT_DIR]
