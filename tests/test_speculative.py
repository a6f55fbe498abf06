"""Speculative decoding (parallel/speculative.py + the verify-K path
through both serving engines).

Pins the ISSUE-7 contract: greedy output TOKEN-IDENTICAL with
speculation on or off (contiguous AND paged engines, windowed
Mistral-tiny included), the rejection-sampling test preserving the
target distribution at temperature > 0, per-request determinism
independent of co-tenant traffic, paged rollback never corrupting a
co-tenant's cache, acceptance metrics/histograms, a bounded program
set (no per-shape retrace), and the persistent compilation cache
satellite (a restarted process demonstrably reuses kernels).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.config import MeshConfig
from tensorlink_tpu.models.llama import Llama, LlamaConfig
from tensorlink_tpu.parallel.inference import (
    GenerationConfig,
    InferenceEngine,
    spec_verify,
)
from tensorlink_tpu.parallel.serving import (
    ContinuousBatchingEngine,
    PagedContinuousBatchingEngine,
    SpecConfig,
)
from tensorlink_tpu.parallel.speculative import (
    SpeculativeDecoder,
    ngram_propose,
)
from tensorlink_tpu.runtime.mesh import make_mesh

KEY = jax.random.key(0)


# ------------------------------------------------------------ unit: verify
def test_spec_verify_greedy_exact_match():
    """Greedy accept/reject is pure argmax comparison: the accepted
    prefix matches the proposals, the emitted token at the first
    rejection is the target's own argmax, all-accept earns the bonus."""
    V, K = 7, 3
    tgt = np.full((K + 1, V), -10.0, np.float32)
    argmax = [2, 5, 1, 6]
    for i, a in enumerate(argmax):
        tgt[i, a] = 0.0
    # all K match -> K+1 emitted, last is the bonus (argmax of row K)
    n, em = spec_verify(jnp.asarray(tgt), jnp.asarray([2, 5, 1]), KEY, 0.0, 0)
    assert int(n) == 4 and list(np.asarray(em)) == [2, 5, 1, 6]
    # mismatch at position 1 -> 2 emitted: proposal 0 + the correction
    n, em = spec_verify(jnp.asarray(tgt), jnp.asarray([2, 4, 1]), KEY, 0.0, 0)
    assert int(n) == 2 and list(np.asarray(em))[:2] == [2, 5]
    # immediate mismatch -> exactly the plain decode step
    n, em = spec_verify(jnp.asarray(tgt), jnp.asarray([0, 5, 1]), KEY, 0.0, 0)
    assert int(n) == 1 and int(np.asarray(em)[0]) == 2


def test_spec_verify_preserves_target_distribution():
    """Rejection sampling at temperature > 0: whatever the draft
    proposes, the FIRST emitted token's marginal distribution is
    exactly the (filtered) target's — the provably-unchanged-output
    property the tentpole rides on."""
    V, K, N = 5, 2, 4000
    r = np.random.default_rng(0)
    tgt = jnp.asarray(r.normal(0, 1.5, (K + 1, V)), jnp.float32)
    drf = jnp.asarray(r.normal(0, 1.5, (K, V)), jnp.float32)
    temp = 0.8
    p_want = np.asarray(jax.nn.softmax(tgt[0] / temp))

    def one(key):
        kp, kv = jax.random.split(key)
        # proposals drawn from the DRAFT distribution, as in serving
        props = jax.random.categorical(kp, drf / temp, axis=-1)
        _, em = spec_verify(tgt, props, kv, temp, 0, 1.0, draft_logits=drf)
        return em[0]

    keys = jax.random.split(jax.random.key(7), N)
    first = np.asarray(jax.vmap(one)(keys))
    emp = np.bincount(first, minlength=V) / N
    # ~4 sigma at N=4000: loose enough to never flake, tight enough to
    # catch a residual-clamping or filtering bug outright
    tol = 4 * np.sqrt(p_want * (1 - p_want) / N)
    np.testing.assert_array_less(np.abs(emp - p_want), tol + 1e-9)


def test_spec_verify_deterministic_ngram_draft():
    """draft_logits=None (delta proposer): acceptance probability is
    the target's own probability of the proposal, and a filtered-out
    proposal (-inf under top-k) is never accepted."""
    V = 6
    tgt = jnp.asarray([[4.0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 4.0]],
                      jnp.float32)
    accepted = 0
    for i in range(200):
        n, em = spec_verify(
            tgt, jnp.asarray([0]), jax.random.key(i), 1.0, 2,
        )
        accepted += int(n) - 1
    # p_target(token 0 at pos 0) ~ softmax([4,0..0])[0] ~ 0.916
    assert 150 <= accepted <= 200


# ------------------------------------------------------------ unit: ngram
def test_ngram_propose_prompt_lookup():
    S, L, k, n = 2, 16, 3, 2
    ids = np.zeros((S, L), np.int32)
    # row 0: ... [7 8] 9 1 2 ... [7 8] pending=8? trailing gram is
    # (last committed, pending): committed [5 6 7 8 9 1 2 7], pending 8
    ids[0, :8] = [5, 6, 7, 8, 9, 1, 2, 7]
    valid = np.zeros((S, L), bool)
    valid[0, :8] = True
    index = np.asarray([8, 3], np.int32)
    tok = np.asarray([8, 9], np.int32)  # row 0 gram (7,8) recurs at 2..3
    ids[1, :3] = [1, 2, 3]
    valid[1, :3] = True  # row 1: gram (3, 9) never occurred
    props, found = ngram_propose(
        jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(index),
        jnp.asarray(tok), k, n,
    )
    props, found = np.asarray(props), np.asarray(found)
    assert bool(found[0]) and list(props[0]) == [9, 1, 2]  # continuation
    assert not bool(found[1]) and list(props[1]) == [9, 9, 9]  # fallback


def test_spec_config_validation_and_vocab_check():
    with pytest.raises(ValueError, match="k must be"):
        SpecConfig(k=0)
    with pytest.raises(ValueError, match="ngram"):
        SpecConfig(ngram=1)
    cfg_t = LlamaConfig.tiny()
    m = Llama(cfg_t)
    eng = InferenceEngine(
        make_mesh(MeshConfig()), m, m.init(KEY), max_len=32,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    cfg_v = LlamaConfig(
        vocab_size=cfg_t.vocab_size * 2, dim=cfg_t.dim,
        num_layers=cfg_t.num_layers, num_heads=cfg_t.num_heads,
        num_kv_heads=cfg_t.num_kv_heads, hidden_dim=cfg_t.hidden_dim,
        max_len=cfg_t.max_len,
    )
    mv = Llama(cfg_v)
    draft = InferenceEngine(
        make_mesh(MeshConfig()), mv, mv.init(KEY), max_len=32,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="vocab"):
        SpeculativeDecoder(eng, draft, SpecConfig())


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def spec_engine():
    """Tiny Llama target + a SAME-ARCH draft with DIFFERENT weights
    (worst-case drafting: near-zero acceptance, so rollback runs
    constantly) + static-engine greedy references."""
    cfg = LlamaConfig.tiny()
    m = Llama(cfg)
    p = m.init(KEY)
    mesh = make_mesh(MeshConfig())
    eng = InferenceEngine(
        mesh, m, p, max_len=32,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    draft = InferenceEngine(
        mesh, m, m.init(jax.random.key(1)), max_len=32,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    gen = GenerationConfig(max_new_tokens=8)
    r = np.random.default_rng(0)
    prompts = [r.integers(0, cfg.vocab_size, (n,)) for n in (5, 3, 7)]
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    return cfg, eng, draft, gen, prompts, refs


# ------------------------------------------------------------ greedy parity
def test_greedy_parity_contiguous(spec_engine):
    """ISSUE-7 acceptance: greedy output token-identical with
    speculation on vs off — n-gram AND draft-model modes, with the
    program set pinned (ONE spec chunk serves any request mix)."""
    cfg, eng, draft, gen, prompts, refs = spec_engine
    for mode_kw in (
        {"speculative": SpecConfig(k=3, rounds=2)},
        {"draft": draft, "speculative": SpecConfig(k=3, rounds=2)},
    ):
        sch = ContinuousBatchingEngine(
            eng, slots=2, gen=gen, decode_chunk=3, prefill_block=4,
            **mode_kw,
        )
        rids = [sch.submit(pr) for pr in prompts]
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(sch.result(rid), ref)
        if hasattr(sch._decode, "_cache_size"):
            warm = sch._decode._cache_size()
            # a different mix of lengths/budgets afterwards: no retrace
            r = np.random.default_rng(9)
            for n in (2, 9, 4, 6):
                sch.submit(
                    r.integers(0, cfg.vocab_size, (n,)),
                    max_new=int(1 + n % 4),
                )
            sch.run_until_idle()
            assert sch._decode._cache_size() == warm == 1


def test_greedy_parity_paged(spec_engine):
    cfg, eng, draft, gen, prompts, refs = spec_engine
    for mode_kw in (
        {"speculative": SpecConfig(k=3, rounds=2)},
        {"draft": draft, "speculative": SpecConfig(k=3, rounds=2)},
    ):
        sch = PagedContinuousBatchingEngine(
            eng, slots=2, gen=gen, block_size=8, num_blocks=16,
            prefill_chunk=8, **mode_kw,
        )
        rids = [sch.submit(pr) for pr in prompts]
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(sch.result(rid), ref)
        assert sch.stats()["spec"]["weight_passes"] > 0
        if hasattr(sch._decode, "_cache_size"):
            # ONE spec-chunk program serves any request mix (paged)
            warm = sch._decode._cache_size()
            r = np.random.default_rng(13)
            for n in (2, 9, 4):
                sch.submit(
                    r.integers(0, cfg.vocab_size, (n,)),
                    max_new=int(1 + n % 4),
                )
            sch.run_until_idle()
            assert sch._decode._cache_size() == warm == 1


def test_windowed_spec_parity():
    """Mistral-tiny (window 8): the verify pass's per-query window band
    in slot space (contiguous) and logical space (paged) must match the
    static engine's — prompts both longer and shorter than the window.
    max_len 288 rounds the cache to 512 slots (> the windowed blockwise
    threshold), so the T=K+1 verify pass exercises the length-bounded
    block loop in BOTH engines, not just the dense fallback."""
    cfg = LlamaConfig.mistral_tiny()
    m = Llama(cfg)
    p = m.init(jax.random.key(3))
    eng = InferenceEngine(
        make_mesh(MeshConfig()), m, p, max_len=288,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    gen = GenerationConfig(max_new_tokens=16)
    r = np.random.default_rng(7)
    prompts = [r.integers(0, cfg.vocab_size, (n,)) for n in (12, 4)]
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    for sch in (
        ContinuousBatchingEngine(
            eng, slots=2, gen=gen, decode_chunk=4, prefill_block=4,
            speculative=SpecConfig(k=3),
        ),
        PagedContinuousBatchingEngine(
            eng, slots=2, gen=gen, block_size=8, num_blocks=24,
            prefill_chunk=8, speculative=SpecConfig(k=3),
        ),
    ):
        rids = [sch.submit(pr) for pr in prompts]
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(sch.result(rid), ref)


# ------------------------------------------------- sampling / determinism
def test_temperature_spec_deterministic_and_traffic_independent(spec_engine):
    """temperature > 0 under speculation: a request's tokens are a
    function of (seed, position) only — identical alone or amid
    co-tenant traffic in a different slot; a different seed differs."""
    cfg, eng, draft, gen0, prompts, refs = spec_engine
    gen = GenerationConfig(max_new_tokens=8, temperature=0.9, top_k=8)
    pr = np.random.default_rng(5).integers(0, cfg.vocab_size, (5,))
    alone = ContinuousBatchingEngine(
        eng, slots=4, gen=gen, decode_chunk=2, prefill_block=4,
        speculative=SpecConfig(k=2),
    )
    a = alone.result(alone.submit(pr, seed=42))
    busy = ContinuousBatchingEngine(
        eng, slots=4, gen=gen, decode_chunk=2, prefill_block=4,
        speculative=SpecConfig(k=2),
    )
    r6 = np.random.default_rng(6)
    for i, n in enumerate((3, 6, 4)):
        busy.submit(r6.integers(0, cfg.vocab_size, (n,)), seed=100 + i)
    b = busy.result(busy.submit(pr, seed=42))
    np.testing.assert_array_equal(a, b)
    assert list(alone.result(alone.submit(pr, seed=43))) != list(a)


# ------------------------------------------------------- paged rollback pin
def test_paged_spec_rollback_no_cross_request_corruption(spec_engine):
    """Extends the PR-5 sentinel-row family: constant rollbacks (the
    mismatched draft rejects nearly everything) while slots churn must
    never touch a co-tenant's blocks — every stream stays token-
    identical to its solo run, and finished slots leave a sentinel
    table + an empty pool."""
    cfg, eng, draft, gen, prompts, refs = spec_engine
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, block_size=4, num_blocks=16,
        prefill_chunk=4, draft=draft,
        speculative=SpecConfig(k=3, rounds=2),
    )
    r = np.random.default_rng(11)
    extra = [r.integers(0, cfg.vocab_size, (n,)) for n in (6, 4, 8)]
    xrefs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in extra]
    rids = [sch.submit(pr) for pr in list(prompts) + extra]
    sch.run_until_idle()
    for rid, ref in zip(rids, list(refs) + xrefs):
        np.testing.assert_array_equal(sch.result(rid), ref)
    st = sch.stats()["spec"]
    assert st["acceptance_rate"] < 0.5  # the rollback path really ran
    assert sch.pool.in_use == 0
    NB = sch.pool.num_blocks
    for c in sch._state["caches"]:
        tbl = np.asarray(c["attn"]["block_table"])
        np.testing.assert_array_equal(tbl, np.full_like(tbl, NB))


# ------------------------------------------------------- metrics / events
def test_spec_metrics_histogram_and_stats(spec_engine):
    from tensorlink_tpu.runtime.metrics import Metrics

    cfg, eng, draft, gen, prompts, refs = spec_engine
    metrics = Metrics()
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4,
        speculative=SpecConfig(k=2), metrics=metrics,
    )
    rids = [sch.submit(pr) for pr in prompts]
    for rid in rids:
        sch.result(rid)
    snap = metrics.snapshot()
    c = snap["counters"]
    # every verified round moved every counter family (rejections are
    # near-certain with prompt-lookup on random tiny-model output)
    assert c.get("spec_rejected_total", 0) > 0
    assert c.get("spec_fallback_total", 0) > 0
    h = snap["histograms"]["serving_spec_acceptance"]
    assert h["n"] == len(prompts)
    st = sch.stats()["spec"]
    assert st["mode"] == "ngram" and st["k"] == 2
    assert st["accepted_tokens_per_weight_pass"] >= 1.0
    assert st["proposed_total"] == st["weight_passes"] * 2
    # per-request accounting adds up to the aggregate
    reqs = list(sch._requests.values())
    assert sum(r.spec_accepted for r in reqs) == st["accepted_total"]
    assert sum(r.spec_proposed for r in reqs) == st["proposed_total"]


def test_high_acceptance_exceeds_one_token_per_pass(spec_engine):
    """The headline lever: a GOOD draft (here: the target itself, the
    acceptance-rate upper bound) emits >> 1 token per target weight
    pass; tldiag's LOW-ACCEPT flag keys off the same stats dict."""
    from tensorlink_tpu.diag import node_row

    cfg, eng, draft, gen, prompts, refs = spec_engine
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4,
        draft=eng, speculative=SpecConfig(k=3, rounds=2),
    )
    rids = [sch.submit(pr) for pr in prompts]
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(sch.result(rid), ref)
    st = sch.stats()["spec"]
    assert st["accepted_tokens_per_weight_pass"] > 2.0
    assert st["acceptance_rate"] > 0.7

    def fake_scrape(spec):
        return {
            "target": "t", "routes": {
                "/healthz": {"body": {"ok": True}},
                "/node": {"body": {"serving": {"spec": spec}}},
            },
        }

    row = node_row(fake_scrape(st), 10.0, 2.0)
    assert row["spec_accept_pct"] == round(st["acceptance_rate"] * 100, 1)
    assert not any(f.startswith("LOW-ACCEPT") for f in row["flags"])
    bad = dict(st, acceptance_rate=0.1)
    row = node_row(fake_scrape(bad), 10.0, 2.0)
    assert any(f.startswith("LOW-ACCEPT") for f in row["flags"])


# ------------------------------------------------- persistent compile cache
_CC_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
from tensorlink_tpu.config import MeshConfig
from tensorlink_tpu.models.llama import Llama, LlamaConfig
from tensorlink_tpu.parallel.inference import GenerationConfig, InferenceEngine
from tensorlink_tpu.parallel.serving import ContinuousBatchingEngine
from tensorlink_tpu.runtime.flight import FlightRecorder
from tensorlink_tpu.runtime.mesh import make_mesh

cfg = LlamaConfig.tiny()
m = Llama(cfg)
p = m.init(jax.random.key(0))
eng = InferenceEngine(
    make_mesh(MeshConfig()), m, p, max_len=16,
    cache_dtype=jnp.float32, param_dtype=jnp.float32,
)
rec = FlightRecorder(max_events=64)
sch = ContinuousBatchingEngine(
    eng, slots=1, gen=GenerationConfig(max_new_tokens=2),
    decode_chunk=2, prefill_block=8, warm_buckets=True,
    prefill_cache_max=1, compile_cache_dir=sys.argv[1], recorder=rec,
)
evs = [e for e in rec.events() if e["kind"] == "serving.compile"]
print(json.dumps([
    {"program": e["attrs"]["program"],
     "hit": e["attrs"].get("compile_cache_hit")}
    for e in evs
]))
"""


def test_compile_cache_restart_reuses_kernels(tmp_path):
    """ROADMAP-5 down payment: two PROCESSES sharing a compile cache
    dir — the first populates it (hits False), the restart compiles
    nothing new (every serving.compile event flags a cache hit)."""
    from tensorlink_tpu.runtime.compile_cache import cache_entries

    d = str(tmp_path / "cc")

    # a directory given from outside would win over the one under test
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _CC_SCRIPT, d],
            capture_output=True, text=True, timeout=600, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert {e["program"] for e in cold} >= {"decode", "prefill"}
    n = cache_entries(d)
    assert n > 0  # the cache actually persisted executables
    warm = run()
    assert cache_entries(d) == n  # restart added NOTHING new
    assert warm and all(e["hit"] for e in warm)


def test_node_config_carries_compile_cache_dir():
    from tensorlink_tpu.config import NodeConfig

    assert NodeConfig().compile_cache_dir is None
    c = NodeConfig(compile_cache_dir="/tmp/x")
    assert c.compile_cache_dir == "/tmp/x"
    assert NodeConfig(autotune_dir="/tmp/y").autotune_dir == "/tmp/y"


# ---------------------------------------------------- adaptive: verify mask
def test_spec_verify_k_live_greedy_masking():
    """Masked K inside the verifier: k_live clamps the accepted prefix
    but every emitted token is still the target's own greedy token —
    the parity property adaptive K rides on."""
    V, K = 7, 3
    tgt = np.full((K + 1, V), -10.0, np.float32)
    for i, a in enumerate([2, 5, 1, 6]):
        tgt[i, a] = 0.0
    props = jnp.asarray([2, 5, 1])  # all would match
    for kl, want_n in ((3, 4), (2, 3), (1, 2), (0, 1)):
        n, em = spec_verify(
            jnp.asarray(tgt), props, KEY, 0.0, 0, k_live=jnp.int32(kl)
        )
        assert int(n) == want_n
        assert list(np.asarray(em))[: int(n)] == [2, 5, 1, 6][: int(n)]


def test_spec_verify_k_live_preserves_distribution():
    """The subtle masked-K case at temperature > 0: a clamped position
    never drew a proposal, so its token must come from the TARGET
    distribution, not the rejection residual — sampling the residual
    there would bias the output exactly when the controller masks."""
    V, K, N = 5, 2, 4000
    r = np.random.default_rng(3)
    tgt = jnp.asarray(r.normal(0, 1.5, (K + 1, V)), jnp.float32)
    drf = jnp.asarray(r.normal(0, 1.5, (K, V)), jnp.float32)
    temp = 0.8
    p_want = np.asarray(jax.nn.softmax(tgt[0] / temp))

    def one(key):
        kp, kv = jax.random.split(key)
        props = jax.random.categorical(kp, drf / temp, axis=-1)
        # k_live = 0: no proposals stand; the single emitted token is
        # a plain decode step and must be EXACTLY target-distributed
        n, em = spec_verify(
            tgt, props, kv, temp, 0, 1.0, draft_logits=drf,
            k_live=jnp.int32(0),
        )
        return em[0] + 0 * n

    keys = jax.random.split(jax.random.key(11), N)
    first = np.asarray(jax.vmap(one)(keys))
    emp = np.bincount(first, minlength=V) / N
    tol = 4 * np.sqrt(p_want * (1 - p_want) / N)
    np.testing.assert_array_less(np.abs(emp - p_want), tol + 1e-9)


# ------------------------------------------------- adaptive: controller law
def test_adaptive_controller_law_and_feedback():
    from tensorlink_tpu.parallel.speculative import AdaptiveKController

    cfg = SpecConfig(k=8, adaptive=True, draft_cost=0.5)
    ctl = AdaptiveKController(cfg)
    # hopeless draft -> floor; perfect draft -> ceiling
    assert ctl.k_for_acceptance(0.0) == cfg.k_min
    assert ctl.k_for_acceptance(0.99) == cfg.k
    # monotone in acceptance
    ks = [ctl.k_for_acceptance(a / 10) for a in range(10)]
    assert ks == sorted(ks)
    # free proposer (n-gram): POSITION_COST alone must still pull K
    # down at low acceptance (else the block-reservation overshoot
    # never tightens)
    free = AdaptiveKController(cfg, draft_cost=0.0)
    assert free.k_for_acceptance(0.01) < cfg.k
    # per-request feedback: rejections walk a request's K down
    rid = 7
    assert ctl.k_for(rid) == ctl.k_for_acceptance(ctl.prior_acceptance)
    for _ in range(30):
        ctl.observe(rid, proposed=8, accepted=0)
    assert ctl.k_for(rid) == cfg.k_min
    # finishing folds into the prior the next request starts from
    before = ctl.prior_acceptance
    ctl.forget(rid)
    assert ctl.prior_acceptance < before
    pr = ctl.prior()
    assert set(pr) == {"k", "acceptance", "draft_cost"}
    # fully-exited rounds (proposed == 0) carry no signal
    ctl.observe(3, proposed=0, accepted=0)
    assert 3 not in ctl._acc


def test_spec_config_adaptive_validation():
    with pytest.raises(ValueError, match="k_min"):
        SpecConfig(k=2, k_min=3)
    with pytest.raises(ValueError, match="entropy_exit"):
        SpecConfig(entropy_exit=0.0)
    with pytest.raises(ValueError, match="self_heal_accept"):
        SpecConfig(self_heal_accept=1.5)
    auto = SpecConfig.auto(k=6)
    assert auto.adaptive and auto.entropy_exit and auto.self_heal_accept


# ------------------------------------------- adaptive: parity + trace count
def test_adaptive_greedy_parity_and_flat_trace_count(spec_engine):
    """ISSUE-12 acceptance: greedy parity adaptive == static-K ==
    non-spec on BOTH engines (the controller changes how many tokens a
    weight pass yields, never which tokens), and per-request K changes
    never grow the program count — K is a traced operand of the ONE
    spec program (tlint TL501 / tlhlo TLH105)."""
    cfg, eng, draft, gen, prompts, refs = spec_engine
    acfg = SpecConfig(k=3, rounds=2, adaptive=True, entropy_exit=6.0)
    for sch in (
        ContinuousBatchingEngine(
            eng, slots=2, gen=gen, decode_chunk=3, prefill_block=4,
            draft=draft, speculative=acfg,
        ),
        PagedContinuousBatchingEngine(
            eng, slots=2, gen=gen, block_size=8, num_blocks=16,
            prefill_chunk=8, draft=draft, speculative=acfg,
        ),
    ):
        rids = [sch.submit(pr) for pr in prompts]
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(sch.result(rid), ref)
        st = sch.stats()["spec"]
        assert st["adaptive"] and st["k_mean"] > 0
        # the mismatched draft drives per-request K DOWN mid-flight —
        # more traffic with churned K values must not retrace
        if hasattr(sch._decode, "_cache_size"):
            warm = sch._decode._cache_size()
            r = np.random.default_rng(17)
            for n in (2, 9, 4, 6):
                sch.submit(
                    r.integers(0, cfg.vocab_size, (n,)),
                    max_new=int(1 + n % 4),
                )
            sch.run_until_idle()
            assert sch._decode._cache_size() == warm == 1
        # audit surface unchanged: still exactly the spec-chunk +
        # prefill programs (no masked-K sibling program appeared)
        names = {p["name"] for p in sch.audit_programs()}
        assert len(names) == 2 and any("spec" in n for n in names)


def test_adaptive_temperature_deterministic(spec_engine):
    """Adaptive K at temperature > 0 keeps the (seed, position)
    determinism contract: same request alone vs amid traffic."""
    cfg, eng, draft, gen0, prompts, refs = spec_engine
    gen = GenerationConfig(max_new_tokens=8, temperature=0.9, top_k=8)
    acfg = SpecConfig(k=2, adaptive=True)
    pr = np.random.default_rng(5).integers(0, cfg.vocab_size, (5,))
    alone = ContinuousBatchingEngine(
        eng, slots=4, gen=gen, decode_chunk=2, prefill_block=4,
        speculative=acfg,
    )
    a = alone.result(alone.submit(pr, seed=42))
    busy = ContinuousBatchingEngine(
        eng, slots=4, gen=gen, decode_chunk=2, prefill_block=4,
        speculative=acfg,
    )
    r6 = np.random.default_rng(6)
    for i, n in enumerate((3, 6, 4)):
        busy.submit(r6.integers(0, cfg.vocab_size, (n,)), seed=100 + i)
    b = busy.result(busy.submit(pr, seed=42))
    np.testing.assert_array_equal(a, b)


# ------------------------------------------------- adaptive: draft early-exit
def test_draft_early_exit_stops_charging_proposals(spec_engine):
    """A paranoid entropy threshold retires every row at step 0: the
    engine degenerates to (correct) non-spec pacing — outputs stay
    token-identical, and the acceptance denominator records ~zero
    attempted proposals instead of charging the draft for positions it
    never stood behind."""
    cfg, eng, draft, gen, prompts, refs = spec_engine
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=3, prefill_block=4,
        draft=draft,
        speculative=SpecConfig(k=3, rounds=2, entropy_exit=1e-4),
    )
    rids = [sch.submit(pr) for pr in prompts]
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(sch.result(rid), ref)
    st = sch.stats()["spec"]
    assert st["weight_passes"] > 0
    # (random tiny-model logits are nowhere near 1e-4 nats of entropy)
    assert st["proposed_total"] == 0
    assert st["accepted_tokens_per_weight_pass"] >= 1.0


# ---------------------------------------------------- self-heal (LOW-ACCEPT)
def test_low_accept_self_heals_without_operator(spec_engine):
    """ISSUE-12 acceptance: under a deliberately bad draft the engine
    drops to n-gram/non-spec ON ITS OWN — on BOTH engines (the paged
    heal must also rebuild its prefill-chunk program and block-table
    ops for the new mode) — and the tldiag cluster row renders
    SELF-HEALED(mode) instead of LOW-ACCEPT."""
    from tensorlink_tpu.diag import node_row

    cfg, eng, draft, gen, prompts, refs = spec_engine
    heal_cfg = SpecConfig(k=3, rounds=2, self_heal_accept=0.3)
    for sch in (
        ContinuousBatchingEngine(
            eng, slots=2, gen=gen, decode_chunk=3, prefill_block=4,
            draft=draft, speculative=heal_cfg,
        ),
        PagedContinuousBatchingEngine(
            eng, slots=2, gen=gen, block_size=8, num_blocks=16,
            prefill_chunk=8, draft=draft, speculative=heal_cfg,
        ),
    ):
        r = np.random.default_rng(23)
        work = list(prompts) + [
            r.integers(0, cfg.vocab_size, (n,)) for n in (6, 5, 7, 4)
        ]
        rids = [sch.submit(pr) for pr in work]
        sch.run_until_idle()
        for rid, ref in zip(rids[: len(refs)], refs):
            np.testing.assert_array_equal(sch.result(rid), ref)
        # the engine measured the draft as a loss and downgraded itself
        healed = sch.stats().get("spec_self_healed")
        assert healed is not None and healed["from"] == "draft"
        assert healed["to"] in ("ngram", "nonspec")
        assert healed["acceptance"] < 0.3
        # post-heal traffic still token-identical (mode changes never
        # change WHICH tokens) — this drives the rebuilt prefill path
        pr2 = r.integers(0, cfg.vocab_size, (5,))
        ref2 = np.asarray(eng.generate(pr2[None], gen))[0]
        np.testing.assert_array_equal(sch.result(sch.submit(pr2)), ref2)
    # sch is the healed paged engine from the loop's last iteration

    def fake_scrape(serving):
        return {
            "target": "t", "routes": {
                "/healthz": {"body": {"ok": True}},
                "/node": {"body": {"serving": serving}},
            },
        }

    st = sch.stats()
    serving = {"spec_self_healed": st["spec_self_healed"]}
    if "spec" in st:
        serving["spec"] = st["spec"]
    row = node_row(fake_scrape(serving), 10.0, 2.0)
    assert any(f.startswith("SELF-HEALED(") for f in row["flags"])
    assert not any(f.startswith("LOW-ACCEPT") for f in row["flags"])


# ------------------------------------------------ paged: tightened slot_ub
def test_adaptive_tightens_block_overshoot_under_rejection(spec_engine):
    """Satellite pin: under constant rejection the static bound
    reserves rounds*(k_max+1) positions ahead of every live frontier
    at every step; the controller's live acceptance estimate shrinks
    per-request K to the floor, so the same traffic holds measurably
    fewer blocks over the run — with outputs still token-identical
    (the bound is tightened by shrinking what the device may emit,
    never by guessing low from drained counts)."""
    from tensorlink_tpu.runtime.metrics import Metrics

    cfg, eng, draft, gen, prompts, refs = spec_engine
    long_gen = GenerationConfig(max_new_tokens=24)
    long_refs = [
        np.asarray(eng.generate(pr[None], long_gen))[0] for pr in prompts
    ]

    def run(spec_cfg):
        m = Metrics()
        sch = PagedContinuousBatchingEngine(
            eng, slots=2, gen=long_gen, block_size=4, num_blocks=64,
            prefill_chunk=4, draft=draft, speculative=spec_cfg,
            metrics=m,
        )
        rids = [sch.submit(pr) for pr in prompts]
        sch.run_until_idle()
        for rid, ref in zip(rids, long_refs):
            np.testing.assert_array_equal(sch.result(rid), ref)
        assert sch.stats()["spec"]["acceptance_rate"] < 0.5  # truly bad
        return sch, m.snapshot()["kv_blocks_in_use"]["mean"]

    _, static_mean = run(SpecConfig(k=3, rounds=2))
    sch, adaptive_mean = run(
        SpecConfig(k=3, rounds=2, adaptive=True, ewma=0.8)
    )
    assert adaptive_mean < static_mean
    # and the bound itself is pinned: with every live request walked
    # down to the floor, the staged dispatch reserves rounds*(k_min+1)
    # positions, not rounds*(k_max+1)
    spec_cfg = sch.spec.cfg
    rid = sch.submit(np.asarray([1, 2, 3], np.int64), max_new=4)
    for _ in range(40):
        sch._kctl.observe(rid, proposed=3, accepted=0)
    slot = next(
        s for s, r in enumerate(sch._slot_req)
        if r is not None and r.rid == rid
    )
    with sch._lock:
        sch._k_dispatch = sch._spec_k_array()
        tight = sch._advance_bound(slot)
        sch._k_dispatch = None
    assert tight == spec_cfg.rounds * (spec_cfg.k_min + 1)
    assert tight < spec_cfg.rounds * (spec_cfg.k + 1)
    sch.result(rid)
