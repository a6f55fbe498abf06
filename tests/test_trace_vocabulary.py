"""The ``tl.`` vocabulary (runtime/tracing.py): what the program writes
into a profiler capture, seen here WITHOUT a capture. Host spans and
events go through one annotation factory, which these tests replace
with a recorder; device names are read from lowered program text. Every
name the hot loops and the programs write has to be in the table; what
older call sites hand ``Tracer.span`` keeps its name and is outside it.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.config import MeshConfig, TrainConfig
from tensorlink_tpu.models.gpt2 import GPT2, GPT2Config
from tensorlink_tpu.models.llama import Llama, LlamaConfig
from tensorlink_tpu.parallel.inference import GenerationConfig, InferenceEngine
from tensorlink_tpu.parallel.serving import (
    ContinuousBatchingEngine,
    PagedContinuousBatchingEngine,
)
from tensorlink_tpu.parallel.speculative import SpecConfig
from tensorlink_tpu.runtime import tracing
from tensorlink_tpu.runtime.mesh import make_mesh
from tensorlink_tpu.runtime.tracing import Tracer, event, known, region, scope
from tensorlink_tpu.train.trainer import Trainer, softmax_cross_entropy

KEY = jax.random.key(0)
CHILDREN = [
    "tl.serve.admit", "tl.serve.prefill_dispatch", "tl.serve.grow_blocks",
    "tl.serve.decode_dispatch", "tl.serve.drain",
]


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: every annotation
    entered, in order, with its arguments and its depth at entry."""

    def __init__(self):
        self.seen: list[dict] = []
        self._open: list[dict] = []

    def __call__(self, name, **attrs):
        rec = self

        class _Ann:
            def __enter__(self):
                me = {
                    "name": name, "attrs": attrs, "depth": len(rec._open),
                    "parent": rec._open[-1]["name"] if rec._open else None,
                    "closed": False,
                }
                rec.seen.append(me)
                rec._open.append(me)
                return me

            def __exit__(self, *exc):
                rec._open.pop()["closed"] = True

        return _Ann()

    def names(self):
        return [e["name"] for e in self.seen]

    def named(self, name):
        return [e for e in self.seen if e["name"] == name]


@pytest.fixture
def rec(monkeypatch):
    r = Recorder()
    monkeypatch.setattr(tracing, "_annotate", r)
    return r


@pytest.fixture(scope="module")
def tiny_trainer():
    model = GPT2(GPT2Config.tiny())

    def loss(module, params, batch, rng):
        return softmax_cross_entropy(
            module.apply(params, batch["input_ids"]), batch["labels"]
        )

    def build(**kw):
        return Trainer(model, loss, TrainConfig(
            batch_size=4, micro_batches=2, learning_rate=1e-3,
            optimizer="adam", grad_clip_norm=1.0,
        ), **kw)

    ids = np.arange(4 * 9).reshape(4, 9) % 128
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]), "labels": jnp.asarray(ids[:, 1:])
    }
    return build, batch


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = LlamaConfig.tiny()
    m = Llama(cfg)
    eng = InferenceEngine(
        make_mesh(MeshConfig()), m, m.init(KEY), max_len=32,
        cache_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    r = np.random.default_rng(3)
    prompts = [r.integers(0, cfg.vocab_size, (n,)) for n in (5, 3, 7, 6)]
    return eng, prompts


def _paged(eng, **kw):
    return PagedContinuousBatchingEngine(
        eng, slots=2, gen=GenerationConfig(max_new_tokens=5), decode_chunk=2,
        block_size=4, prefill_chunk=4, **kw,
    )


# ------------------------------------------------------------ the table
def test_every_table_entry_is_whole():
    kinds = set()
    for name, (kind, layer, bounds) in tracing.VOCABULARY.items():
        kinds.add(kind)
        assert layer and bounds and "\n" not in layer + bounds, name
        assert known(name) and known("tl." + name)
    assert kinds == {"span", "event", "scope", "program"}


@pytest.mark.parametrize("name,ok", [
    ("tl.serve.step", True), ("train.step", True), ("attn", True),
    ("serve.sleep", False), ("tl.attention", False), ("bench.window", False),
    # Tracer.span's older names reach a capture but are not the table's
    ("rpc.PING", False), ("stage3.fwd", False), ("trainer.step", False),
])
def test_known(name, ok):
    assert known(name) is ok


# -------------------------------------------------------- the primitives
def test_region_is_an_annotation_and_no_span_without_a_tracer(rec):
    with region("serve.step", slots=2) as s:
        assert s is None
    assert rec.seen == [{
        "name": "tl.serve.step", "attrs": {"slots": 2}, "depth": 0,
        "parent": None, "closed": True,
    }]


def test_region_with_a_tracer_records_the_span_too(rec):
    t = Tracer("test")
    with region("serve.step", t, turn=1) as outer:
        with region("serve.drain", t) as inner:
            assert tracing.current_span() is inner
    assert t.spans() == [inner, outer]
    assert (outer.name, inner.name) == ("serve.step", "serve.drain")
    assert outer.attrs == {"turn": 1} and outer.parent_id is None
    assert inner.parent_id == outer.span_id and inner.trace_id == outer.trace_id
    assert rec.names() == ["tl.serve.step", "tl.serve.drain"]
    assert tracing.current_span() is None


def test_region_without_a_tracer_records_nothing_under_a_span(rec):
    """The hot loops' phases are for a capture: a span that is ambient
    (an RPC handler stepping the engine) does not pull them into its
    tracer's buffer."""
    t = Tracer("node")
    with t.span("rpc.GENERATE") as rpc:
        with region("serve.step") as s:
            assert s is None and tracing.current_span() is rpc
            event("serve.admitted", rid=1, waited_ms=0.0)
    assert [x.name for x in t.spans()] == ["rpc.GENERATE"]
    assert rec.names() == ["tl.rpc.GENERATE", "tl.serve.step", "tl.serve.admitted"]
    assert [e["depth"] for e in rec.seen] == [0, 1, 2]


def test_tracer_span_goes_through_the_same_primitive(rec):
    t = Tracer("test")
    with pytest.raises(KeyError):
        with t.span("rpc.PING", {"peer": "ab"}):
            raise KeyError("x")
    (s,) = t.spans()
    assert s.status == "error" and s.attrs == {"peer": "ab", "error": "KeyError"}
    assert rec.seen[0]["name"] == "tl.rpc.PING" and rec.seen[0]["closed"]
    assert rec.seen[0]["attrs"] == {"peer": "ab"}


def test_event_is_an_instant_with_arguments(rec):
    event("serve.admitted", rid=7, waited_ms=1.5)
    (e,) = rec.seen
    assert e["name"] == "tl.serve.admitted" and e["closed"]
    assert e["attrs"] == {"rid": 7, "waited_ms": 1.5}


def test_scope_is_in_the_op_path_forward_and_backward():
    def f(x):
        with scope("mlp"):
            return jnp.sum(jnp.tanh(x) * 2.0)

    text = jax.jit(jax.grad(f)).lower(jnp.ones((4,))).as_text(debug_info=True)
    assert "jvp(tl.mlp)" in text and "transpose(jvp(tl.mlp))" in text


def test_the_real_annotation_factory_takes_the_arguments():
    """No stand-in: jax's own TraceAnnotation, outside a capture."""
    tracing._annotate = None
    with region("train.step", step=3):
        event("serve.first_token", rid=1, ttft_ms=2.0)
    assert tracing._annotate is jax.profiler.TraceAnnotation


# ------------------------------------------------------------- training
@pytest.mark.parametrize("telemetry", [False, True])
def test_train_step_emits_its_span(rec, tiny_trainer, telemetry):
    build, batch = tiny_trainer
    tracer = Tracer("train") if telemetry else None
    tr = build(tracer=tracer)
    state = tr.init_state(KEY)
    for _ in range(2):
        state, stats = tr.train_step(state, batch, KEY)
    assert np.isfinite(float(stats["loss"]))
    assert len(rec.named("tl.train.step")) == 2
    if telemetry:
        # StepTelemetry's span is the recorded one; tl.train.step, the
        # jitted call inside it, is the capture's
        assert rec.names() == [
            "tl.trainer.compile_step", "tl.train.step",
            "tl.trainer.step", "tl.train.step",
        ]
        assert [e["parent"] for e in rec.named("tl.train.step")] == [
            "tl.trainer.compile_step", "tl.trainer.step"
        ]
        assert [s.name for s in tracer.spans()] == [
            "trainer.compile_step", "trainer.step"
        ]
        with tr.data_span():
            pass
        assert rec.names()[-1] == "tl.trainer.data"
        assert tracer.spans()[-1].name == "trainer.data"
    else:
        assert set(rec.names()) == {"tl.train.step"}
        assert all(known(n) for n in rec.names())


@pytest.fixture(scope="module")
def train_step_text(tiny_trainer):
    build, batch = tiny_trainer
    tr = build()
    state = tr.init_state(KEY)
    return tr.audit_programs(state, batch, KEY)[0]["lower"]().as_text(
        debug_info=True
    )


def test_train_program_has_its_own_name(train_step_text):
    assert re.search(r"module @jit_tl_train_step\b", train_step_text)


@pytest.mark.parametrize("name", [
    "tl.embed", "tl.attn", "tl.mlp", "tl.head", "tl.loss", "tl.train.cast",
    "tl.train.accumulate", "tl.train.sentinel", "tl.train.clip",
    "tl.train.optimizer",
])
def test_train_program_holds_the_scope(train_step_text, name):
    assert known(name) and tracing.VOCABULARY[name[3:]][0] == "scope"
    assert re.search(rf"[/(]{re.escape(name)}[/)]", train_step_text)
    if name in ("tl.attn", "tl.mlp", "tl.head", "tl.loss"):
        # backward instructions keep the scope
        assert f"transpose(jvp({name}))" in train_step_text


def test_every_scope_in_the_train_program_is_in_the_table(train_step_text):
    found = set(re.findall(r"tl\.[a-z_.]*[a-z]", train_step_text))
    assert found and all(known(n) for n in found), found


# ------------------------------------------- a model of unlike layers
KIMI_SCOPES = [
    "tl.kda", "tl.kda.scan", "tl.mla", "tl.moe", "tl.moe.route",
    "tl.moe.experts",
]


@pytest.fixture(scope="module")
def kimi_step_text():
    """Kimi-Linear's tiny preset through ``Trainer``: KDA and MLA
    mixers, a dense and four expert feed-forwards, blocks recomputed."""
    import dataclasses

    from tensorlink_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig

    model = KimiLinear(dataclasses.replace(KimiLinearConfig.tiny(), remat=True))

    def loss(module, params, batch, rng):
        return softmax_cross_entropy(
            module.apply(params, batch["input_ids"]), batch["labels"]
        )

    tr = Trainer(model, loss, TrainConfig(
        batch_size=2, micro_batches=1, learning_rate=1e-3, optimizer="adam",
        grad_clip_norm=1.0,
    ))
    ids = np.arange(2 * 33).reshape(2, 33) % 128
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]), "labels": jnp.asarray(ids[:, 1:])
    }
    state = jax.eval_shape(tr.init_state, KEY)
    return tr.audit_programs(state, batch, KEY)[0]["lower"]().as_text(
        debug_info=True
    )


@pytest.mark.parametrize("name", KIMI_SCOPES + ["tl.mlp", "tl.embed", "tl.head"])
def test_kimi_train_program_holds_the_scope(kimi_step_text, name):
    assert known(name) and tracing.VOCABULARY[name[3:]][0] == "scope"
    assert re.search(rf"[/(]{re.escape(name)}[/)]", kimi_step_text)
    if name in ("tl.embed", "tl.head"):
        return
    # backward instructions keep the scope, under the block's remat too:
    # jit(tl_train_step)/transpose(jvp(...))/checkpoint/.../tl.kda/...
    paths = set(re.findall(r'"(jit\(tl_train_step\)[^"]*)"', kimi_step_text))
    assert any(
        "transpose(" in p and re.search(rf"/{re.escape(name)}(/|$)", p)
        for p in paths
    )


def test_every_scope_in_the_kimi_program_is_in_the_table(kimi_step_text):
    found = set(re.findall(r"tl\.[a-z_.]*[a-z]", kimi_step_text))
    assert set(KIMI_SCOPES) <= found and all(known(n) for n in found), found
    assert "tl.attn" not in found  # no block of the uniform trunk here


def test_a_kimi_block_instruction_reads_one_half(kimi_step_text):
    """Inside a block every instruction lies under the scope of the
    half it belongs to: the nested scopes only inside their parent, and
    no instruction under two halves."""
    halves = ("tl.kda", "tl.mla", "tl.moe", "tl.mlp")
    for path in set(re.findall(r'"(jit\(tl_train_step\)[^"]*)"', kimi_step_text)):
        scopes = re.findall(r"tl\.[a-z_.]*[a-z]", path)
        inner = [s for s in scopes if s.startswith(halves)]
        if not inner:
            continue
        assert len({s.split(".")[1] for s in inner}) == 1, path
        for child, parent in (("tl.kda.scan", "tl.kda"),
                              ("tl.moe.route", "tl.moe"),
                              ("tl.moe.experts", "tl.moe")):
            if child in inner:
                assert parent in inner[:inner.index(child)], path


# --------------------- a model whose layers read what earlier ones gave
PHI4_SCOPES = ["tl.mamba", "tl.mamba.scan", "tl.gmu", "tl.attn"]


@pytest.fixture(scope="module")
def phi4_step_text():
    """Phi-4-mini-flash's tiny preset through ``Trainer``: two Mamba
    mixers, window, full and cross differential attention, a gated
    memory unit, blocks recomputed."""
    import dataclasses

    from tensorlink_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig

    model = Phi4Flash(dataclasses.replace(Phi4FlashConfig.tiny(), remat=True))

    def loss(module, params, batch, rng):
        return softmax_cross_entropy(
            module.apply(params, batch["input_ids"]), batch["labels"]
        )

    tr = Trainer(model, loss, TrainConfig(
        batch_size=2, micro_batches=1, learning_rate=1e-3, optimizer="adam",
        grad_clip_norm=1.0,
    ))
    ids = np.arange(2 * 33).reshape(2, 33) % 128
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]), "labels": jnp.asarray(ids[:, 1:])
    }
    state = jax.eval_shape(tr.init_state, KEY)
    return tr.audit_programs(state, batch, KEY)[0]["lower"]().as_text(
        debug_info=True
    )


@pytest.mark.parametrize("name", PHI4_SCOPES + ["tl.mlp", "tl.embed", "tl.head"])
def test_phi4_train_program_holds_the_scope(phi4_step_text, name):
    assert known(name) and tracing.VOCABULARY[name[3:]][0] == "scope"
    assert re.search(rf"[/(]{re.escape(name)}[/)]", phi4_step_text)
    if name in ("tl.embed", "tl.head"):
        return
    # backward instructions keep the scope, under the block's remat and
    # the scan's own backward rule too
    paths = set(re.findall(r'"(jit\(tl_train_step\)[^"]*)"', phi4_step_text))
    assert any(
        "transpose(" in p and re.search(rf"/{re.escape(name)}(/|$)", p)
        for p in paths
    )


def test_every_scope_in_the_phi4_program_is_in_the_table(phi4_step_text):
    found = set(re.findall(r"tl\.[a-z_.]*[a-z]", phi4_step_text))
    assert set(PHI4_SCOPES) <= found and all(known(n) for n in found), found
    assert not found & {"tl.kda", "tl.mla", "tl.moe"}


def test_a_phi4_block_instruction_reads_one_half(phi4_step_text):
    """Inside a block every instruction lies under the scope of the
    half it belongs to, the scan only inside ``tl.mamba``, and no
    instruction under two halves."""
    halves = ("tl.mamba", "tl.gmu", "tl.attn", "tl.mlp")
    seen = set()
    for path in set(re.findall(r'"(jit\(tl_train_step\)[^"]*)"', phi4_step_text)):
        scopes = re.findall(r"tl\.[a-z_.]*[a-z]", path)
        inner = [s for s in scopes if s.startswith(halves)]
        if not inner:
            continue
        seen.update(inner)
        assert len({s.split(".")[1] for s in inner}) == 1, path
        if "tl.mamba.scan" in inner:
            assert "tl.mamba" in inner[:inner.index("tl.mamba.scan")], path
    assert seen == set(halves) | {"tl.mamba.scan"}


def test_sharded_trainer_names_its_program_and_span(rec):
    from tensorlink_tpu.parallel.engine import ShardedTrainer

    model = GPT2(GPT2Config.tiny())
    mesh = make_mesh(MeshConfig())
    parts = model.as_pipeline_parts(model.init(KEY))
    tr = ShardedTrainer(
        mesh, TrainConfig(batch_size=4, micro_batches=2, learning_rate=1e-3),
        parts, lambda out, b: softmax_cross_entropy(out, b["labels"]),
    )
    state = tr.init_state()
    ids = np.arange(4 * 9).reshape(4, 9) % 128
    batch = {"input_ids": jnp.asarray(ids[:, :-1]), "labels": jnp.asarray(ids[:, 1:])}
    text = tr.audit_programs(state, batch)[0]["lower"]().as_text(debug_info=True)
    assert re.search(r"module @jit_tl_sharded_train_step\b", text)
    for name in ("tl.embed", "tl.attn", "tl.mlp", "tl.head", "tl.train.optimizer"):
        assert name in text
    state, _ = tr.train_step(state, batch)
    assert rec.names() == ["tl.train.step"]


# -------------------------------------------------------------- serving
def test_paged_engine_step_has_its_children_in_order(rec, tiny_engine):
    eng, prompts = tiny_engine
    sch = _paged(eng)
    rids = [sch.submit(p) for p in prompts]
    sch.run_until_idle()
    assert all(len(sch.result(r)) == 5 for r in rids)
    assert all(known(n) for n in rec.names()), set(rec.names())
    steps = rec.named("tl.serve.step")
    assert steps and all(s["closed"] for s in rec.seen)
    whole = 0
    for i, s in enumerate(rec.seen):
        if s["name"] != "tl.serve.step":
            continue
        kids = []
        for e in rec.seen[i + 1:]:
            if e["depth"] <= s["depth"]:
                break
            if e["depth"] == s["depth"] + 1 and e["name"] in CHILDREN:
                kids.append(e["name"])
        # a turn with nothing to decode skips that child, never the order
        assert kids in (CHILDREN, CHILDREN[:3] + CHILDREN[4:]), kids
        whole += kids == CHILDREN
    assert whole >= 2
    # spans other than the step's children never sit directly under it
    assert {
        e["name"] for e in rec.seen if e["parent"] == "tl.serve.step"
    } <= set(CHILDREN) | {"tl.serve.admitted", "tl.serve.first_token"}


def test_one_admitted_and_one_first_token_per_request(rec, tiny_engine):
    eng, prompts = tiny_engine
    sch = _paged(eng)
    rids = [sch.submit(p) for p in prompts]
    sch.run_until_idle()
    adm = {e["attrs"]["rid"]: e["attrs"] for e in rec.named("tl.serve.admitted")}
    first = {
        e["attrs"]["rid"]: e["attrs"] for e in rec.named("tl.serve.first_token")
    }
    assert len(rec.named("tl.serve.admitted")) == len(rids) == len(adm)
    assert len(rec.named("tl.serve.first_token")) == len(rids) == len(first)
    assert sorted(adm) == sorted(first) == sorted(rids)
    for rid in rids:
        assert 0 <= adm[rid]["waited_ms"] <= first[rid]["ttft_ms"]
    # the two queued behind two slots waited for a slot; the first did not
    assert adm[rids[0]]["waited_ms"] < adm[rids[-1]]["waited_ms"]
    # the first token is held before the step's drain ends
    assert all(
        e["parent"] == "tl.serve.drain" for e in rec.named("tl.serve.first_token")
    )


def test_a_preempted_request_is_admitted_once_in_the_capture(rec, tiny_engine):
    """A pool too small for the live set preempts and resumes: the
    second slot grant and the re-prefill's token are no new events."""
    from tensorlink_tpu.runtime.metrics import Metrics

    eng, _ = tiny_engine
    metrics = Metrics()
    sch = PagedContinuousBatchingEngine(
        eng, slots=2, gen=GenerationConfig(max_new_tokens=8), decode_chunk=2,
        block_size=4, prefill_chunk=4, num_blocks=5, prefix_cache=False,
        metrics=metrics,
    )
    r = np.random.default_rng(24)
    rids = [sch.submit(r.integers(0, 64, (n,))) for n in (6, 7)]
    sch.run_until_idle()
    assert all(len(sch.result(x)) == 8 for x in rids)
    assert metrics.snapshot()["counters"]["serving_preempt_total"] >= 1
    for name in ("tl.serve.admitted", "tl.serve.first_token"):
        assert sorted(e["attrs"]["rid"] for e in rec.named(name)) == sorted(rids)


def test_the_export_path_stamps_the_first_token(rec, tiny_engine):
    """Disaggregated prefill: the host holds the first token when it
    reads it back for the payload, and no decode turn ever runs."""
    eng, prompts = tiny_engine
    sch = _paged(eng)
    payload = sch.prefill_export(prompts[0])
    assert payload is not None
    (adm,), (first,) = rec.named("tl.serve.admitted"), rec.named("tl.serve.first_token")
    assert adm["attrs"]["rid"] == first["attrs"]["rid"]
    assert 0 <= adm["attrs"]["waited_ms"] <= first["attrs"]["ttft_ms"]
    assert "tl.serve.decode_dispatch" not in rec.names()


def test_contiguous_engine_step_children(rec, tiny_engine):
    eng, prompts = tiny_engine
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=GenerationConfig(max_new_tokens=4), decode_chunk=2,
        prefill_block=4,
    )
    rids = [sch.submit(p) for p in prompts]
    sch.run_until_idle()
    assert all(known(n) for n in rec.names())
    assert "tl.serve.grow_blocks" not in rec.names()
    under_step = [e["name"] for e in rec.seen if e["parent"] == "tl.serve.step"]
    assert under_step[:3] == [
        "tl.serve.admit", "tl.serve.decode_dispatch", "tl.serve.drain"
    ]
    # admission IS the prefill dispatch on this engine
    assert {e["parent"] for e in rec.named("tl.serve.prefill_dispatch")} <= {
        "tl.serve.admit", None  # None: submit() admits into a free slot
    }
    assert len(rec.named("tl.serve.prefill_dispatch")) == len(rids)
    assert len(rec.named("tl.serve.admitted")) == len(rids)
    assert len(rec.named("tl.serve.first_token")) == len(rids)


@pytest.mark.parametrize("kind", ["paged", "contiguous"])
def test_a_tracer_holds_the_requests_and_not_the_turns(rec, tiny_engine, kind):
    """A node's span buffer (2048 spans, shared with its RPC spans) is
    for per-request timelines: an engine's turns, six spans each, go to
    the capture alone."""
    eng, prompts = tiny_engine
    tracer = Tracer("serve")
    if kind == "paged":
        sch = _paged(eng, tracer=tracer)
    else:
        sch = ContinuousBatchingEngine(
            eng, slots=2, gen=GenerationConfig(max_new_tokens=5),
            decode_chunk=2, prefill_block=4, tracer=tracer,
        )
    rids = [sch.submit(p) for p in prompts]
    sch.run_until_idle()
    assert all(len(sch.result(r)) == 5 for r in rids)
    names = [s.name for s in tracer.spans()]
    # the per-request timeline still feeds /spans, stitched at finish
    assert names.count("serving.request") == len(rids)
    assert {"serving.queue_wait", "serving.decode"} <= set(names)
    assert all(n.startswith("serving.") for n in names), set(names)
    turns = len(rec.named("tl.serve.step"))
    assert turns > len(rids) and len(names) <= 5 * len(rids)
    # stitched spans are recorded from stamps, not entered: the capture
    # has the turns and the two events, and no span per request
    assert not any(n.startswith("tl.serving.") for n in rec.names())


def test_an_idle_turn_still_has_its_phases(rec, tiny_engine):
    eng, _ = tiny_engine
    sch = _paged(eng)
    assert sch.step() is False
    assert rec.names() == [
        "tl.serve.step", "tl.serve.admit", "tl.serve.prefill_dispatch",
        "tl.serve.grow_blocks", "tl.serve.drain",
    ]
    assert [e["depth"] for e in rec.seen] == [0, 1, 1, 1, 1]


def _module_names(progs):
    return {
        p["name"]: re.search(r"module @(\w+)", p["lower"]().as_text()).group(1)
        for p in progs
    }


@pytest.mark.parametrize("kind,kw,expect", [
    ("paged", {}, {"decode": "jit_tl_decode",
                   "prefill_chunk": "jit_tl_prefill_chunk"}),
    ("paged", {"speculative": SpecConfig(k=2)},
     {"spec_chunk": "jit_tl_spec_chunk",
      "prefill_chunk_spec": "jit_tl_prefill_chunk"}),
    ("contiguous", {}, {"decode": "jit_tl_decode",
                        "prefill_b4": "jit_tl_prefill"}),
])
def test_serving_programs_have_their_own_names(tiny_engine, kind, kw, expect):
    eng, _ = tiny_engine
    gen = GenerationConfig(max_new_tokens=4)
    if kind == "paged":
        sch = PagedContinuousBatchingEngine(
            eng, slots=2, gen=gen, decode_chunk=2, block_size=4,
            prefill_chunk=4, **kw,
        )
    else:
        sch = ContinuousBatchingEngine(
            eng, slots=2, gen=gen, decode_chunk=2, prefill_block=4, **kw
        )
    got = _module_names(sch.audit_programs())
    assert got == expect
    assert all(known(v.removeprefix("jit_tl_")) for v in got.values())


def test_decode_program_holds_the_serving_scopes(tiny_engine):
    eng, _ = tiny_engine
    text = _paged(eng).audit_programs()[0]["lower"]().as_text(debug_info=True)
    for name in ("tl.serve.sample", "tl.serve.cache_write", "tl.attn",
                 "tl.mlp", "tl.embed", "tl.head"):
        assert name in text, name
    assert all(known(n) for n in set(re.findall(r"tl\.[a-z_.]*[a-z]", text)))


@pytest.mark.parametrize("what", ["table", "retire", "copy", "graft", "adopt"])
def test_pool_programs_are_named(tiny_engine, what):
    eng, _ = tiny_engine
    sch = _paged(eng)
    fn = getattr(sch, f"_build_{what}_op")()
    assert fn.__name__ == f"tl_pool_{what}" and known(f"pool_{what}")
