"""Continuous-batching serving engine (parallel/serving.py).

Pins the scheduler's contract: token-level greedy parity with the
static engine, slot-exhaustion backpressure, mid-stream EOS freeing a
slot that is immediately re-admitted, typed rejection of prompts that
cannot fit a slot's cache region, per-request RNG streams that are
independent of slot assignment and co-tenant traffic, and TTFT/TPOT
metrics through the Metrics registry.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.config import MeshConfig
from tensorlink_tpu.models.llama import Llama, LlamaConfig
from tensorlink_tpu.parallel.inference import GenerationConfig, InferenceEngine
from tensorlink_tpu.parallel.serving import (
    ContinuousBatchingEngine,
    PagedContinuousBatchingEngine,
    PoolExhaustedError,
    PromptTooLongError,
    QueueFullError,
)
from tensorlink_tpu.runtime.mesh import make_mesh

KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = LlamaConfig.tiny()
    m = Llama(cfg)
    p = m.init(KEY)
    eng = InferenceEngine(
        make_mesh(MeshConfig()), m, p, max_len=32,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return cfg, m, p, eng


def _prompts(cfg, lengths, seed=0):
    r = np.random.default_rng(seed)
    return [r.integers(0, cfg.vocab_size, (n,)) for n in lengths]


def test_greedy_parity_with_static_engine(tiny_engine):
    """Staggered prompts of mixed lengths through 2 slots must produce
    EXACTLY the tokens the static engine produces for each prompt alone
    (greedy): the acceptance bar for continuous batching."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(cfg, (5, 3, 7, 4, 6, 2))
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=3, prefill_block=4
    )
    rids = [sch.submit(pr) for pr in prompts]
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(sch.result(rid), ref)


def test_slot_exhaustion_backpressures_queue(tiny_engine):
    """More requests than slots: the overflow queues (no error, no loss)
    and every request still completes with correct tokens."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=5)
    prompts = _prompts(cfg, (4, 4, 4, 4, 4, 4, 4), seed=1)
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4
    )
    rids = [sch.submit(pr) for pr in prompts]
    assert sch.stats()["queued"] >= len(prompts) - 2  # admission is lazy
    sch.run_until_idle()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(sch.result(rid), ref)


def test_max_queue_raises_typed_error(tiny_engine):
    cfg, m, p, eng = tiny_engine
    sch = ContinuousBatchingEngine(
        eng, slots=1, gen=GenerationConfig(max_new_tokens=4),
        prefill_block=4, max_queue=1,
    )
    pr = _prompts(cfg, (4,))[0]
    sch.submit(pr)
    sch.submit(pr)  # first pending admission fills the queue
    with pytest.raises(QueueFullError):
        sch.submit(pr)


def test_eos_frees_slot_for_immediate_readmission(tiny_engine):
    """A request ending at EOS mid-stream releases its slot; a queued
    request is admitted into that same slot and decodes correctly."""
    cfg, m, p, eng = tiny_engine
    pr_a, pr_b = _prompts(cfg, (5, 6), seed=3)
    free = np.asarray(
        eng.generate(pr_a[None], GenerationConfig(max_new_tokens=8))
    )[0]
    eos = int(free[2])  # the 3rd generated token becomes "eos"
    gen = GenerationConfig(max_new_tokens=8, eos_token_id=eos)
    ref_a = np.asarray(eng.generate(pr_a[None], gen))[0]
    ref_b = np.asarray(eng.generate(pr_b[None], gen))[0]
    sch = ContinuousBatchingEngine(
        eng, slots=1, gen=gen, decode_chunk=2, prefill_block=4
    )
    ra, rb = sch.submit(pr_a), sch.submit(pr_b)
    out_a, out_b = sch.result(ra), sch.result(rb)
    # a ends early at eos; engine output pads with eos after termination
    assert out_a[-1] == eos and len(out_a) == 3
    np.testing.assert_array_equal(out_a, ref_a[: len(out_a)])
    # b re-used the single slot after a's EOS; must match its solo run
    # up to ITS eos point
    stop = len(out_b)
    assert stop == 8 or out_b[-1] == eos
    np.testing.assert_array_equal(out_b, ref_b[:stop])
    assert sch.stats()["busy_slots"] == 0


def test_prompt_too_long_typed_rejection(tiny_engine):
    cfg, m, p, eng = tiny_engine
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=GenerationConfig(max_new_tokens=8), prefill_block=4
    )
    with pytest.raises(PromptTooLongError):
        sch.submit(np.arange(40))  # > max_len outright
    with pytest.raises(PromptTooLongError):
        sch.submit(np.arange(28))  # prompt + max_new > cache region
    with pytest.raises(ValueError):
        sch.submit(np.arange(0))  # empty prompt
    # a fitting prompt still serves after the rejections
    ok = sch.submit(np.arange(4) % cfg.vocab_size)
    assert len(sch.result(ok)) == 8


def test_per_request_rng_independent_of_traffic(tiny_engine):
    """Sampling keys derive from (request seed, logical position) only:
    the same request yields the same tokens alone on 4 slots and amid
    co-tenant traffic in a different slot."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=8, temperature=0.9, top_k=8)
    pr = _prompts(cfg, (5,), seed=5)[0]
    alone = ContinuousBatchingEngine(
        eng, slots=4, gen=gen, decode_chunk=2, prefill_block=4
    )
    a = alone.result(alone.submit(pr, seed=42))
    busy = ContinuousBatchingEngine(
        eng, slots=4, gen=gen, decode_chunk=2, prefill_block=4
    )
    others = _prompts(cfg, (3, 6, 4), seed=6)
    for i, o in enumerate(others):
        busy.submit(o, seed=100 + i)
    b = busy.result(busy.submit(pr, seed=42))
    np.testing.assert_array_equal(a, b)
    # a different seed actually changes the draw
    c = alone.result(alone.submit(pr, seed=43))
    assert list(c) != list(a)


@pytest.fixture(scope="module")
def windowed_engine():
    """Mistral-tiny (window 8) engine + static-engine reference outputs,
    shared by the contiguous and paged windowed-parity tests (the model
    init and reference generates compile once per module)."""
    cfg = LlamaConfig.mistral_tiny()  # window 8
    m = Llama(cfg)
    p = m.init(jax.random.key(3))
    eng = InferenceEngine(
        make_mesh(MeshConfig()), m, p, max_len=64,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    gen = GenerationConfig(max_new_tokens=16)
    prompts = _prompts(cfg, (12, 4), seed=7)  # prompt > window and <
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    return eng, gen, prompts, refs


def test_windowed_model_parity(windowed_engine):
    """Sliding-window model (monotone cache) through the scheduler: the
    per-row window band must match the engine's scalar-index band."""
    eng, gen, prompts, refs = windowed_engine
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=4, prefill_block=4
    )
    rids = [sch.submit(pr) for pr in prompts]
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(sch.result(rid), ref)


def test_max_new_one_and_per_request_budget(tiny_engine):
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    pr = _prompts(cfg, (5,), seed=8)[0]
    ref = np.asarray(eng.generate(pr[None], gen))[0]
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=3, prefill_block=4
    )
    r1 = sch.submit(pr, max_new=1)
    r2 = sch.submit(pr, max_new=4)
    np.testing.assert_array_equal(sch.result(r1), ref[:1])
    np.testing.assert_array_equal(sch.result(r2), ref[:4])


def test_ttft_tpot_metrics_and_counters(tiny_engine):
    from tensorlink_tpu.runtime.metrics import Metrics

    cfg, m, p, eng = tiny_engine
    metrics = Metrics()
    gen = GenerationConfig(max_new_tokens=5)
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4,
        metrics=metrics,
    )
    prompts = _prompts(cfg, (4, 5, 3), seed=9)
    rids = [sch.submit(pr) for pr in prompts]
    for rid in rids:
        sch.result(rid)
    snap = metrics.snapshot()
    assert snap["counters"]["serving_requests_total"] == 3
    assert snap["counters"]["serving_tokens_total"] == 15
    h = snap["histograms"]
    assert h["serving_ttft_s"]["n"] == 3
    assert h["serving_tpot_s"]["n"] == 3
    assert h["serving_ttft_s"]["sum"] > 0


def test_user_node_serving_engine_wires_observability(tiny_engine):
    """The user role's local inference path: serving through
    UserNode.serving_engine lands TTFT/TPOT in the node's /metrics
    registry and request lifecycle events in its flight recorder."""
    from tensorlink_tpu.config import NodeConfig
    from tensorlink_tpu.roles.user import UserNode

    cfg, m, p, eng = tiny_engine
    node = UserNode(NodeConfig(role="user", host="127.0.0.1", port=0))
    sch = node.serving_engine(
        eng, slots=2, gen=GenerationConfig(max_new_tokens=4),
        prefill_block=4,
    )
    pr = _prompts(cfg, (4,), seed=10)[0]
    out = sch.result(sch.submit(pr))
    assert len(out) == 4
    assert node.metrics.histograms["serving_ttft_s"].n == 1
    kinds = [e["kind"] for e in node.flight.events()]
    for k in ("serving.submit", "serving.admit", "serving.finish"):
        assert k in kinds, kinds


def test_rejects_rolling_and_seq_sharded_engines(devices):
    cfg = LlamaConfig.mistral_tiny()
    m = Llama(cfg)
    p = m.init(jax.random.key(1))
    ring = InferenceEngine(
        make_mesh(MeshConfig()), m, p, max_len=32,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
        rolling_cache=True,
    )
    with pytest.raises(NotImplementedError, match="rolling"):
        ContinuousBatchingEngine(ring, slots=2)
    cfg2 = LlamaConfig.tiny()
    m2 = Llama(cfg2)
    sharded = InferenceEngine(
        make_mesh(MeshConfig(seq=4)), m2, m2.init(jax.random.key(2)),
        max_len=32, cache_dtype=jnp.float32, param_dtype=jnp.float32,
        kv_seq_shard=True,
    )
    with pytest.raises(NotImplementedError, match="kv_seq_shard"):
        ContinuousBatchingEngine(sharded, slots=2)


def test_result_retention_bounded(tiny_engine):
    """Finished requests stay readable (result() is idempotent) until
    keep_results newer completions evict them — host memory must not
    grow with total traffic."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=3)
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4,
        keep_results=2,
    )
    prompts = _prompts(cfg, (4, 4, 4, 4), seed=12)
    rids = [sch.submit(pr) for pr in prompts]
    sch.run_until_idle()
    # newest two readable, twice
    for rid in rids[-2:]:
        a = sch.result(rid)
        np.testing.assert_array_equal(a, sch.result(rid))
    for rid in rids[:2]:
        with pytest.raises(KeyError, match="evicted"):
            sch.result(rid)
    assert sch.stats()["requests"] <= 2


def test_async_result_wrapper(tiny_engine):
    import asyncio

    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=4)
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4
    )
    ref = np.asarray(
        eng.generate(_prompts(cfg, (4,), seed=11)[0][None], gen)
    )[0]

    async def go():
        rid = await sch.asubmit(_prompts(cfg, (4,), seed=11)[0])
        return await sch.aresult(rid, timeout_s=120)

    np.testing.assert_array_equal(asyncio.run(go()), ref)


# ---------------------------------------------------- paged KV cache


def test_paged_greedy_parity_with_contiguous_and_static(tiny_engine):
    """ISSUE-6 acceptance: the paged engine's output is token-identical
    to the contiguous scheduler AND the static engine for the same
    prompts/seeds (greedy)."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(cfg, (5, 3, 7, 4, 6, 2))
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    cont = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=3, prefill_block=4
    )
    paged = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=3, block_size=4,
        prefill_chunk=4,
    )
    crids = [cont.submit(pr) for pr in prompts]
    prids = [paged.submit(pr) for pr in prompts]
    for crid, prid, ref in zip(crids, prids, refs):
        np.testing.assert_array_equal(cont.result(crid), ref)
        np.testing.assert_array_equal(paged.result(prid), ref)


def test_paged_windowed_model_parity(windowed_engine):
    """Sliding-window model through block tables: the window band folds
    in logical coordinates and must match the static engine."""
    eng, gen, prompts, refs = windowed_engine
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=4, block_size=8,
        prefill_chunk=8,
    )
    rids = [sch.submit(pr) for pr in prompts]
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(sch.result(rid), ref)


@pytest.fixture(scope="module")
def paged_small(tiny_engine):
    """One slots=2 paged engine shared by the prefix-sharing and COW
    tests: its decode/prefill-chunk programs compile once per module.
    The tests use disjoint prompt sets and metric DELTAS, so each holds
    standalone and in any order."""
    from tensorlink_tpu.runtime.metrics import Metrics

    cfg, m, p, eng = tiny_engine
    metrics = Metrics()
    gen = GenerationConfig(max_new_tokens=6)
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4, metrics=metrics,
    )
    return gen, metrics, sch


def test_paged_shared_prefix_skips_prefill(tiny_engine, paged_small):
    """N requests sharing a system prompt: every request after the
    first maps the resident prefix blocks (hit rate > 0), the count of
    actually-prefilled tokens drops below the submitted prompt tokens,
    and outputs stay token-identical to solo runs."""
    cfg, m, p, eng = tiny_engine
    gen, metrics, sch = paged_small
    r = np.random.default_rng(21)
    sys_prompt = r.integers(0, cfg.vocab_size, (12,))
    prompts = [
        np.concatenate([sys_prompt, r.integers(0, cfg.vocab_size, (n,))])
        for n in (3, 4, 2)
    ]
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    matched0 = sch.prefix_matched_tokens
    prefilled0 = sch.prefilled_tokens
    prompt0 = sch.prompt_tokens_total
    hits0 = metrics.snapshot()["counters"].get("prefix_hits_total", 0)
    # sequential so each prefill registers before the next submit
    for pr, ref in zip(prompts, refs):
        np.testing.assert_array_equal(sch.result(sch.submit(pr)), ref)
    assert sch.prefix_hit_rate() > 0
    assert (
        sch.prefilled_tokens - prefilled0
        < sch.prompt_tokens_total - prompt0
    )
    # 2 sharers x the 3 resident system-prompt blocks
    assert sch.prefix_matched_tokens - matched0 >= 2 * 12
    snap = metrics.snapshot()
    assert snap["counters"]["prefix_hits_total"] - hits0 >= 2 * 12


def test_paged_cow_preserves_sharers_tokens(tiny_engine, paged_small):
    """Copy-on-write: while request A still decodes (its partial tail
    block is LIVE-shared), request B whose prompt EXTENDS A's matches
    that tail and must COW it before writing its own continuation —
    without the copy, B's prefill and A's decode would scribble
    different tokens over the same block offsets. A's shared k/v bytes
    stay intact and both outputs match their solo refs."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    r = np.random.default_rng(22)
    pra = r.integers(0, cfg.vocab_size, (10,))  # 2 full blocks + fill 2
    prb = np.concatenate([pra, r.integers(0, cfg.vocab_size, (2,))])
    ref_a = np.asarray(eng.generate(pra[None], gen))[0]
    ref_b = np.asarray(eng.generate(prb[None], gen))[0]
    from tensorlink_tpu.runtime.metrics import Metrics

    metrics = Metrics()
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4, metrics=metrics,
    )
    ra = sch.submit(pra)
    # drive A's prefill to completion (registers the prefix) but keep
    # it decoding so its blocks stay live-shared
    while sch._pending:
        sch.step()
    tail_bid = sch._slot_blocks[sch._requests[ra].slot][-1]
    # the registered fill region (A's prompt tokens 8..9) of the shared
    # tail block, BEFORE the sharer arrives
    k_fill = np.asarray(
        sch._state["caches"][0]["attn"]["k"][tail_bid, :2]
    )
    rb = sch.submit(prb)  # matches A's LIVE partial tail -> COW
    out_a, out_b = sch.result(ra), sch.result(rb)
    np.testing.assert_array_equal(out_a, ref_a)
    np.testing.assert_array_equal(out_b, ref_b)
    assert metrics.snapshot()["counters"]["kv_cow_copies_total"] >= 1
    assert metrics.snapshot()["counters"]["prefix_hits_total"] >= 10
    # A's shared bytes are byte-for-byte what A's prefill wrote
    np.testing.assert_array_equal(
        k_fill,
        np.asarray(sch._state["caches"][0]["attn"]["k"][tail_bid, :2]),
    )


def test_paged_pool_exhaustion_typed_backpressure(tiny_engine):
    """A request that can NEVER fit raises PoolExhaustedError at
    submit; a full queue behind a starved pool raises it too (instead
    of QueueFullError) — typed backpressure, not a shape error."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=4)
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4, num_blocks=3, max_queue=1,
    )
    with pytest.raises(PoolExhaustedError, match="pool holds 3"):
        sch.submit(np.arange(12) % cfg.vocab_size)  # needs 4 blocks
    # a fitting request serves fine afterwards
    pr = _prompts(cfg, (4,), seed=23)[0]
    ref = np.asarray(eng.generate(pr[None], gen))[0]
    np.testing.assert_array_equal(sch.result(sch.submit(pr)), ref)


def test_paged_preemption_keeps_streams_token_identical(tiny_engine):
    """A pool too small for the live set preempts the newest request;
    its blocks free, it re-queues, and the resumed stream is
    token-identical (sampling keys depend on position, not history)."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=8)
    r = np.random.default_rng(24)
    pra = r.integers(0, cfg.vocab_size, (6,))
    prb = r.integers(0, cfg.vocab_size, (7,))
    refa = np.asarray(eng.generate(pra[None], gen))[0]
    refb = np.asarray(eng.generate(prb[None], gen))[0]
    from tensorlink_tpu.runtime.metrics import Metrics

    metrics = Metrics()
    # 5 blocks of 4 cannot hold both requests' worst case (4 each):
    # decode growth must preempt and resume
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4, num_blocks=5, prefix_cache=False,
        metrics=metrics,
    )
    ra, rb = sch.submit(pra), sch.submit(prb)
    np.testing.assert_array_equal(sch.result(ra), refa)
    np.testing.assert_array_equal(sch.result(rb), refb)
    assert metrics.snapshot()["counters"]["serving_preempt_total"] >= 1


def test_paged_finish_retires_device_block_table(tiny_engine):
    """A finished slot's device block-table row must go to the sentinel
    BEFORE its blocks return to the pool: the decode program scatter-
    writes every row (parked included), so a stale table would keep
    writing the dead request's last k/v into blocks the pool may have
    handed to another request (cross-request cache corruption)."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=4)
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4,
    )
    pr = _prompts(cfg, (6,), seed=27)[0]
    rid = sch.submit(pr)
    req = sch._requests[rid]
    sch.result(rid)
    slot = next(
        s for s in range(2) if sch._slot_req[s] is None and not sch._slot_blocks[s]
    )
    assert req.done and not sch._slot_blocks[slot]
    NB = sch.pool.num_blocks
    for c in sch._state["caches"]:
        tbl = np.asarray(c["attn"]["block_table"][slot])
        np.testing.assert_array_equal(tbl, np.full_like(tbl, NB))


def test_paged_no_head_of_line_bypass_on_submit(tiny_engine):
    """A submit that arrives while the queue head is starved on blocks
    must wait BEHIND it (FIFO), even when a slot is free — otherwise
    steady small-prompt traffic starves a queued long prompt forever."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=4)
    # pool of 3: the live 8-token request pins 2 blocks, so a second
    # 8-token prompt (needs 2 now) starves with a slot still free
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4, num_blocks=3, prefix_cache=False,
    )
    pra, prlong, prb = _prompts(cfg, (8, 8, 3), seed=28)
    refs = [
        np.asarray(eng.generate(pr[None], gen))[0]
        for pr in (pra, prlong, prb)
    ]
    ra = sch.submit(pra)       # 2 of 3 blocks + slot 0
    rlong = sch.submit(prlong)  # needs 2, free 1: starved, queues
    rb = sch.submit(prb)       # fits (needs 1) but must NOT jump ahead
    assert sch._slot_req.count(None) == 1  # a slot IS free
    assert [r.rid for r in sch._queue] == [rlong, rb]
    outs = {r: sch.result(r) for r in (ra, rlong, rb)}
    for r, ref in zip((ra, rlong, rb), refs):
        np.testing.assert_array_equal(outs[r], ref)


def test_paged_programs_shape_static_across_request_mixes(tiny_engine):
    """ISSUE-6 acceptance: block tables/indices are traced operands, so
    the compiled-program counts must NOT grow with the request mix —
    one decode chunk + one prefill chunk program serve any traffic."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    sch = PagedContinuousBatchingEngine(
        eng, slots=3, gen=gen, decode_chunk=3, block_size=4,
        prefill_chunk=4,
    )
    r = np.random.default_rng(25)
    for n in (5, 3, 7, 4):
        sch.submit(r.integers(0, cfg.vocab_size, (n,)))
    sch.run_until_idle()
    progs = (sch._decode, sch._prefill_chunk_fn, sch._table_op,
             sch._retire_op, sch._copy_op)
    if not all(hasattr(f, "_cache_size") for f in progs):
        pytest.skip("jax build without PjitFunction._cache_size")
    warm = [f._cache_size() for f in progs]
    assert warm[0] >= 1 and warm[1] >= 1
    # a wildly different mix of prompt lengths and budgets afterwards
    for n in (11, 2, 9, 6, 13, 1, 8, 5, 10, 3):
        sch.submit(
            r.integers(0, cfg.vocab_size, (n,)), max_new=int(1 + n % 5)
        )
    sch.run_until_idle()
    assert [f._cache_size() for f in progs] == warm


@pytest.fixture(scope="module")
def engine_program_counts(tiny_engine):
    """Both engines, plain and speculating, each driven through a
    churned mix of lengths and budgets; the paged one also through a
    copy-on-write and an imported prefill, so every pool program runs.
    Per engine: how often each of its programs was traced and
    compiled."""
    from conftest import counting_programs
    from tensorlink_tpu.parallel.serving import SpecConfig

    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    spec = SpecConfig(k=3, rounds=2)
    paged = dict(
        slots=2, gen=gen, decode_chunk=2, block_size=4, prefill_chunk=4
    )
    contiguous = dict(slots=2, gen=gen, decode_chunk=3, prefill_block=4)
    r = np.random.default_rng(31)

    def churn(sch):
        for n in (5, 3, 7, 2, 9, 4, 6):
            sch.submit(
                r.integers(0, cfg.vocab_size, (n,)), max_new=int(1 + n % 5)
            )
        sch.run_until_idle()

    def pool_traffic(sch):
        # a prompt that extends a live one's partial tail block: a copy
        pra = r.integers(0, cfg.vocab_size, (10,))
        ra = sch.submit(pra)
        while sch._pending:
            sch.step()
        rb = sch.submit(
            np.concatenate([pra, r.integers(0, cfg.vocab_size, (2,))])
        )
        sch.result(ra), sch.result(rb)
        # a prefill another engine ran: a graft and an adoption, twice
        for payload in exported:
            sch.result(sch.import_prefill(payload))
        churn(sch)

    out = {}
    with counting_programs() as counts:
        exporter = PagedContinuousBatchingEngine(eng, **paged)
        exported = [
            exporter.prefill_export(r.integers(0, cfg.vocab_size, (n,)))
            for n in (9, 5)
        ]
        for label, cls, kw, drive in (
            ("contiguous", ContinuousBatchingEngine, contiguous, churn),
            ("contiguous_spec", ContinuousBatchingEngine,
             dict(contiguous, speculative=spec), churn),
            ("paged", PagedContinuousBatchingEngine, paged, pool_traffic),
            ("paged_spec", PagedContinuousBatchingEngine,
             dict(paged, speculative=spec), churn),
        ):
            counts.clear()
            drive(cls(eng, **kw))
            out[label] = (dict(counts.traces), dict(counts.compiles))
    return out


# every jitted program the two engines build (the contiguous prefill
# is one a bucket), and the engines of the fixture that build it
ENGINE_PROGRAMS = {
    "tl_decode": {"contiguous", "paged"},
    "tl_spec_chunk": {"contiguous_spec", "paged_spec"},
    "tl_prefill_chunk": {"paged", "paged_spec"},
    "tl_pool_table": {"paged", "paged_spec"},
    "tl_pool_retire": {"paged", "paged_spec"},
    "tl_pool_copy": {"paged"},
    "tl_pool_graft": {"paged"},
    "tl_pool_adopt": {"paged"},
}


@pytest.mark.parametrize("name", ENGINE_PROGRAMS)
def test_engine_program_traced_and_compiled_once(engine_program_counts, name):
    """One trace and one compilation of each program an engine builds,
    whatever the traffic: a second costs a compile in production, at
    real widths tens of seconds with requests waiting."""
    ran = {
        label: (traces[name], compiles.get(name, 0))
        for label, (traces, compiles) in engine_program_counts.items()
        if name in traces
    }
    assert ran == {label: (1, 1) for label in ENGINE_PROGRAMS[name]}


def test_paged_chunked_prefill_does_not_stall_decode(tiny_engine):
    """A long arriving prompt prefills in fixed chunks interleaved with
    decode dispatches: the in-flight request keeps gaining tokens WHILE
    the new prompt is still mid-prefill (bounded TPOT, no full-prompt
    stall)."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=12)
    r = np.random.default_rng(26)
    pra = r.integers(0, cfg.vocab_size, (4,))
    prb = r.integers(0, cfg.vocab_size, (16,))  # 8 prefill chunks of 2
    refa = np.asarray(eng.generate(pra[None], gen))[0]
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=2, pipeline_depth=0,  # drain per step: observable
    )
    ra = sch.submit(pra)
    sch.step()  # A finishes prefill
    sch.step()  # A decodes
    rb = sch.submit(prb)
    req_a = sch._requests[ra]
    gained = 0
    while sch._pending and not req_a.done:
        before = len(req_a.tokens)
        sch.step()  # one prefill chunk for B + one decode chunk for A
        gained += len(req_a.tokens) - before
    assert gained >= 3 * sch.decode_chunk  # A progressed during B's prefill
    np.testing.assert_array_equal(sch.result(ra), refa)
    np.testing.assert_array_equal(
        sch.result(rb), np.asarray(eng.generate(prb[None], gen))[0]
    )


def test_paged_footprint_scales_with_live_tokens(tiny_engine):
    """HBM accounting: peak blocks track live tokens (prompt + budget),
    nowhere near the contiguous slots*max_len reservation; everything
    is freed once traffic drains."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=4)
    sch = PagedContinuousBatchingEngine(
        eng, slots=4, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4,
    )
    prompts = _prompts(cfg, (4, 4, 4, 4), seed=27)
    rids = [sch.submit(pr) for pr in prompts]
    sch.run_until_idle()
    for rid in rids:
        assert len(sch.result(rid)) == 4
    # 4 live requests x ceil((4+4)/4)=2 blocks each = 8 blocks peak,
    # vs the contiguous reservation of slots*L/bs = 32
    assert sch.peak_blocks_in_use <= 8
    assert sch.peak_blocks_in_use * sch.block_size < sch.slots * sch.L
    assert sch.pool.in_use == 0  # block-granular free on finish
    assert all(pool_ref == 0 for pool_ref in sch.pool._refs)


def test_paged_rejects_bad_geometry(tiny_engine):
    cfg, m, p, eng = tiny_engine
    with pytest.raises(ValueError, match="must divide"):
        PagedContinuousBatchingEngine(eng, slots=2, block_size=5)
    with pytest.raises(ValueError, match="block_size"):
        PagedContinuousBatchingEngine(eng, slots=2, block_size=0)
    with pytest.raises(PromptTooLongError):
        sch = PagedContinuousBatchingEngine(
            eng, slots=2, gen=GenerationConfig(max_new_tokens=8),
            block_size=4,
        )
        sch.submit(np.arange(30) % cfg.vocab_size)  # 30+8 > L=32


def test_prefill_bucket_cache_bounded_lru(tiny_engine):
    """The contiguous engine's per-bucket prefill cache is a bounded
    LRU: adversarial prompt-length mixes cannot grow host memory."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=2)
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4,
        prefill_cache_max=2,
    )
    for n in (3, 7, 11):  # three distinct buckets (4, 8, 12)
        sch.result(sch.submit(_prompts(cfg, (n,), seed=n)[0]))
    assert len(sch._prefill_jit) == 2
    assert 4 not in sch._prefill_jit  # oldest bucket evicted


def test_warm_buckets_records_compile_events(tiny_engine):
    """warm_buckets=True pre-compiles the decode + prefill programs at
    construction and logs compile_s per program to the flight recorder
    (the ROADMAP-5 cold-start number)."""
    from tensorlink_tpu.runtime.flight import FlightRecorder

    cfg, m, p, eng = tiny_engine
    rec = FlightRecorder(max_events=64)
    gen = GenerationConfig(max_new_tokens=3)
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, prefill_block=8,
        warm_buckets=True, prefill_cache_max=3, recorder=rec,
    )
    compiles = [
        e for e in rec.events() if e["kind"] == "serving.compile"
    ]
    assert any(e["attrs"]["program"] == "decode" for e in compiles)
    buckets = [
        e["attrs"]["bucket"] for e in compiles
        if e["attrs"]["program"] == "prefill"
    ]
    assert buckets == [8, 16, 24]  # smallest-first, capped by the LRU
    assert all(e["attrs"]["compile_s"] >= 0 for e in compiles)
    # warmed engine still serves correctly
    pr = _prompts(cfg, (5,), seed=28)[0]
    ref = np.asarray(eng.generate(pr[None], gen))[0]
    np.testing.assert_array_equal(sch.result(sch.submit(pr)), ref)
    # paged engine warms its (single) prefill-chunk + decode programs
    rec2 = FlightRecorder(max_events=64)
    psch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4, warm_buckets=True, recorder=rec2,
    )
    kinds = [
        e["attrs"]["program"] for e in rec2.events()
        if e["kind"] == "serving.compile"
    ]
    assert set(kinds) == {"decode", "prefill_chunk"}
    np.testing.assert_array_equal(psch.result(psch.submit(pr)), ref)


def test_paged_user_node_exposes_pool_in_status(tiny_engine):
    """UserNode.serving_engine(paged=True) attaches the scheduler so
    GET /node carries pool stats — what tldiag's KV-PRESSURE flag
    reads."""
    from tensorlink_tpu.config import NodeConfig
    from tensorlink_tpu.roles.user import UserNode

    cfg, m, p, eng = tiny_engine
    node = UserNode(NodeConfig(role="user", host="127.0.0.1", port=0))
    sch = node.serving_engine(
        eng, paged=True, slots=2,
        gen=GenerationConfig(max_new_tokens=4), block_size=4,
        prefill_chunk=4,
    )
    pr = _prompts(cfg, (4,), seed=29)[0]
    assert len(sch.result(sch.submit(pr))) == 4
    st = node.status()
    pool = st["serving"]["pool"]
    assert pool["num_blocks"] > 0 and pool["blocks_in_use"] == 0
    assert st["serving"]["prefix_cache_hit_rate"] == 0.0
    kinds = [e["kind"] for e in node.flight.events()]
    assert "serving.prefill_chunk" in kinds


def test_stats_and_result_lock_safe_under_concurrent_stepping(tiny_engine):
    """Regression for the TL601 lock-skew fixes: stats() /
    prefix_hit_rate() / result() take the scheduler lock, so a metrics
    scraper thread racing the decode loop sees consistent (never torn,
    never crashing) snapshots. Hammers a scraper thread against a live
    paged scheduler and pins monotonic admission counters."""
    import threading

    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=4)
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
        prefill_chunk=4,
    )
    prompts = _prompts(cfg, (5, 3, 6, 4, 5, 3))
    errors: list = []
    seen: list = []
    stop = threading.Event()

    def scrape():
        try:
            while not stop.is_set():
                s = sch.stats()
                # consistency inside one snapshot: matched <= submitted
                assert (
                    s["prefix_matched_tokens"] <= s["prompt_tokens_total"]
                )
                seen.append(s["prompt_tokens_total"])
                sch.prefix_hit_rate()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    t = threading.Thread(target=scrape)
    t.start()
    try:
        rids = [sch.submit(pr) for pr in prompts]
        for rid in rids:
            assert len(sch.result(rid)) > 0  # locked lookup + pump
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors, errors
    # the counter the scraper watched never went backwards
    assert all(a <= b for a, b in zip(seen, seen[1:]))
    assert sch.stats()["prompt_tokens_total"] == sum(
        len(pr) for pr in prompts
    )


# ------------------------------- paged kernel + int8 KV blocks (ISSUE 20)


@contextlib.contextmanager
def _paged_kernel_env(mode):
    """Pin TL_PAGED_KERNEL for the engines built inside the block. The
    flag is read at trace time, so it must be set BEFORE the engine
    traces its programs (fresh engine per mode)."""
    old = os.environ.get("TL_PAGED_KERNEL")
    os.environ["TL_PAGED_KERNEL"] = mode
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("TL_PAGED_KERNEL", None)
        else:
            os.environ["TL_PAGED_KERNEL"] = old


def _paged_tokens(eng, gen, prompts, *, kv_quant=None, spec=None,
                  block_size=4, prefill_chunk=4):
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=gen, decode_chunk=3, block_size=block_size,
        prefill_chunk=prefill_chunk, kv_quant=kv_quant, speculative=spec,
    )
    rids = [sch.submit(pr) for pr in prompts]
    return [np.asarray(sch.result(rid)) for rid in rids]


def test_paged_kernel_greedy_parity_and_kill_switch(tiny_engine):
    """ISSUE-20 acceptance: the block-table-native kernel (interpret
    emulation on CPU) produces the same greedy tokens as the static
    engine, and TL_PAGED_KERNEL=0 restores the pure-XLA gather path
    bit-for-bit (token-identical to the default CPU path)."""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(cfg, (5, 3, 7, 4))
    refs = [np.asarray(eng.generate(pr[None], gen))[0] for pr in prompts]
    with _paged_kernel_env("0"):
        off = _paged_tokens(eng, gen, prompts)
    with _paged_kernel_env("interpret"):
        on = _paged_tokens(eng, gen, prompts)
    for o, k, ref in zip(off, on, refs):
        np.testing.assert_array_equal(o, ref)  # kill switch == XLA ref
        np.testing.assert_array_equal(k, ref)  # kernel == XLA ref


def test_paged_int8_greedy_parity_xla_and_kernel(tiny_engine):
    """int8 KV blocks (write-time scales, dequantize-at-read): both
    read paths — the XLA gather fallback and the interpret-mode kernel
    — produce IDENTICAL greedy tokens over the same quantized pools.
    (Token identity vs the float reference is NOT the contract on a
    random tiny model: near-tied argmaxes flip under any KV
    perturbation — quality vs float is bounded by the KL gate in
    test_quant.py instead.)"""
    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(cfg, (5, 3, 7, 4))
    with _paged_kernel_env("0"):
        xla = _paged_tokens(eng, gen, prompts, kv_quant="int8")
    with _paged_kernel_env("interpret"):
        kern = _paged_tokens(eng, gen, prompts, kv_quant="int8")
    for x, k in zip(xla, kern):
        assert len(x) > 0
        np.testing.assert_array_equal(x, k)


def test_paged_int8_kernel_spec_mode_parity(tiny_engine):
    """Speculative decode drives the kernel's T>1 verify widths
    (T = K+1): spec over int8 pools + kernel must be LOSSLESS — token
    stream identical to the same engine decoding without speculation
    (rejected drafts roll the index back; the quantized slots they
    wrote are dead and re-written)."""
    from tensorlink_tpu.parallel.serving import SpecConfig

    cfg, m, p, eng = tiny_engine
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(cfg, (5, 3, 7, 4))
    with _paged_kernel_env("interpret"):
        plain = _paged_tokens(eng, gen, prompts, kv_quant="int8")
        spec = _paged_tokens(
            eng, gen, prompts, kv_quant="int8", spec=SpecConfig(k=3)
        )
    for s, ref in zip(spec, plain):
        np.testing.assert_array_equal(s, ref)


def test_paged_int8_windowed_parity(windowed_engine):
    """Mistral-tiny (window 8): the kernel folds the window band in
    logical coordinates over int8 pools — parity with the static
    engine for prompts longer and shorter than the window, on both
    read paths."""
    eng, gen, prompts, refs = windowed_engine
    with _paged_kernel_env("0"):
        xla = _paged_tokens(
            eng, gen, prompts, kv_quant="int8",
            block_size=8, prefill_chunk=8,
        )
    with _paged_kernel_env("interpret"):
        kern = _paged_tokens(
            eng, gen, prompts, kv_quant="int8",
            block_size=8, prefill_chunk=8,
        )
    for x, k, ref in zip(xla, kern, refs):
        np.testing.assert_array_equal(x, ref)
        np.testing.assert_array_equal(k, ref)


def test_paged_int8_rejects_unknown_quant(tiny_engine):
    cfg, m, p, eng = tiny_engine
    with pytest.raises(ValueError, match="quant"):
        PagedContinuousBatchingEngine(
            eng, slots=2, block_size=4, kv_quant="fp8"
        )
