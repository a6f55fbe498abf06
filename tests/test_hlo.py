"""tlhlo — the compiled-program auditor (tensorlink_tpu/analysis/hlo.py).

Fixture HLO/StableHLO texts pin each rule family's parse + verdict in
isolation; small REAL jitted programs pin the end-to-end audit path
(including the acceptance scenario: a deliberately dropped
``donate_argnums`` is caught by TLH101); one module-scoped canonical
audit proves the full enumeration stays clean against the committed
``hlo.manifest.json``.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from tensorlink_tpu.analysis.hlo import (
    HLO_RULES,
    MANIFEST_NAME,
    ProgramAudit,
    StableStats,
    audit_findings,
    audit_lowered,
    check_collectives,
    check_donation,
    check_dtype,
    check_host_calls,
    check_memory,
    find_default_manifest,
    load_manifest,
    parse_alias_count,
    parse_hlo,
    parse_stablehlo,
    render_findings,
    run_audit,
    write_manifest,
)

# ------------------------------------------------------------ fixture texts
_HLO_ALIASED = """\
HloModule jit_f, is_scheduled=true, input_output_alias={ {0}: (1, {}, \
may-alias), {1}: (2, {}, may-alias) }, entry_computation_layout={(f32[4])}

ENTRY %main (p0: f32[4], p1: f32[4], p2: f32[4]) -> (f32[4], f32[4]) {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  %p2 = f32[4]{0} parameter(2)
  %add.1 = f32[4]{0} add(f32[4]{0} %p1, f32[4]{0} %p0)
  %mul.1 = f32[4]{0} multiply(f32[4]{0} %p2, f32[4]{0} %p0)
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%add.1, %mul.1)
}
"""

_HLO_NO_ALIAS = _HLO_ALIASED.replace(
    "input_output_alias={ {0}: (1, {}, may-alias), {1}: (2, {}, "
    "may-alias) }, ",
    "",
)

# a sharded program: a small (admitted) gather, a big (cache-sized) one,
# an all-reduce, and a fusion whose OPERAND mentions the gather (must
# not double-count), plus sharded cache writes
_HLO_COLLECTIVES = """\
HloModule jit_g, is_scheduled=true

ENTRY %main (p0: bf16[2,512,4,16]) -> bf16[2,2048,4,16] {
  %p0 = bf16[2,512,4,16]{3,2,1,0} parameter(0)
  %upd = bf16[2,512,4,16]{3,2,1,0} dynamic-update-slice(bf16[2,512,4,16]{3,2,1,0} %p0, bf16[2,1,4,16]{3,2,1,0} %p0, s32[] %c, s32[] %c, s32[] %c, s32[] %c)
  %small = f32[2,4]{1,0} all-reduce(f32[2,4]{1,0} %x), to_apply=%sum
  %ag.1 = bf16[2,2048,4,16]{3,2,1,0} all-gather(bf16[2,512,4,16]{3,2,1,0} %upd), dimensions={1}
  %ags = (bf16[2,512,4,16]{3,2,1,0}, bf16[2,2048,4,16]{3,2,1,0}) all-gather-start(bf16[2,512,4,16]{3,2,1,0} %upd), dimensions={1}
  %agd = bf16[2,2048,4,16]{3,2,1,0} all-gather-done((bf16[2,512,4,16]{3,2,1,0}, bf16[2,2048,4,16]{3,2,1,0}) %ags)
  %fused = bf16[2,2048,4,16]{3,2,1,0} fusion(bf16[2,2048,4,16]{3,2,1,0} %ag.1), kind=kLoop, calls=%fc
  ROOT %out = bf16[2,2048,4,16]{3,2,1,0} copy(bf16[2,2048,4,16]{3,2,1,0} %fused)
}
"""

_STABLE_BF16_CLEAN = """\
module @jit_f {
  func.func public @main(%arg0: tensor<8x16xbf16>) -> tensor<8x16xbf16> {
    %0 = stablehlo.dot_general %arg0, %arg0 : (tensor<8x16xbf16>, tensor<8x16xbf16>) -> tensor<8x8xbf16>
    %1 = stablehlo.convert %0 : (tensor<8x8xbf16>) -> tensor<8x8xf32>
    %2 = stablehlo.convert %1 : (tensor<8x8xf32>) -> tensor<8x8xbf16>
    return %arg0 : tensor<8x16xbf16>
  }
}
"""

_STABLE_F32_DOT = _STABLE_BF16_CLEAN.replace(
    "-> tensor<8x8xbf16>\n", "-> tensor<8x8xf32>\n", 1
)

_STABLE_HOST = """\
module @jit_f {
  func.func public @main(%arg0: tensor<4xbf16>) -> tensor<4xf32> {
    %0 = stablehlo.convert %arg0 : (tensor<4xbf16>) -> tensor<4xf32>
    %1 = stablehlo.custom_call @xla_python_cpu_callback(%0) {has_side_effect = true} : (tensor<4xf32>) -> tuple<>
    %2:2 = "stablehlo.infeed"(%t) : (!stablehlo.token) -> (tensor<2xf32>, !stablehlo.token)
    return %0 : tensor<4xf32>
  }
}
"""


# ----------------------------------------------------------------- parsing
def test_parse_alias_count():
    assert parse_alias_count(_HLO_ALIASED) == 2
    assert parse_alias_count(_HLO_NO_ALIAS) == 0


def test_parse_hlo_ops_and_queries():
    ir = parse_hlo(_HLO_COLLECTIVES)
    # operand mentions and -done forms don't count; fusion isn't a
    # gather; the async -start form folds into the base kind
    assert ir.count("all-gather") == 2
    assert ir.count("all-reduce") == 1
    assert ir.count("dynamic-update-slice", dtype="bf16",
                    shape=(2, 512, 4, 16)) == 1
    assert ir.has_result("bf16", (2, 2048, 4, 16))
    assert not ir.has_result("bf16", (2, 4096, 4, 16))
    by_kind = ir.collective_bytes()
    assert by_kind["all-gather"] == 2 * 2048 * 4 * 16 * 2  # bf16 = 2 B
    assert by_kind["all-reduce"] == 2 * 4 * 4
    # the async form's TUPLE result records the materialized (gathered)
    # element, not the input shard — a 4x under-measure otherwise
    starts = [op for op in ir.ops if op.kind == "all-gather-start"]
    assert [op.shape for op in starts] == [(2, 2048, 4, 16)]


def test_variadic_sync_collective_records_largest_element():
    """XLA's combiner merges gradient all-reduces into ONE variadic
    (tuple-result) sync op; recording the first tuple element would pin
    the budget at the smallest operand."""
    txt = (
        "HloModule jit_h, is_scheduled=true\n\n"
        "ENTRY %main () -> (f32[4], f32[1048576]) {\n"
        "  %ar = (f32[4]{0}, f32[1048576]{0}) all-reduce("
        "f32[4]{0} %a, f32[1048576]{0} %b), to_apply=%sum\n"
        "}\n"
    )
    ir = parse_hlo(txt)
    assert ir.collective_bytes() == {"all-reduce": 1048576 * 4}


def test_tuple_collective_of_more_than_five_elements_is_parsed():
    """XLA marks every fifth element of a tuple type (``/*index=5*/``);
    the CPU backend's all-to-all over 8 devices is such a tuple, and
    a parser that stops at the mark's ``=`` counts no collective."""
    elems = ", ".join(
        ("/*index=5*/" if i == 5 else "") + "f32[1,32,1,1,5]{4,3,2,1,0}"
        for i in range(8)
    )
    txt = (
        "HloModule jit_h, is_scheduled=true\n\n"
        "ENTRY %main () -> f32[4] {\n"
        f"  %all-to-all.2 = ({elems}) all-to-all(%a, %b), "
        "replica_groups={{0,1,2,3,4,5,6,7}}\n"
        "}\n"
    )
    ir = parse_hlo(txt)
    assert ir.count("all-to-all") == 1
    assert ir.collective_bytes() == {"all-to-all": 32 * 5 * 4}


def test_parse_stablehlo_counts():
    clean = parse_stablehlo(_STABLE_BF16_CLEAN)
    assert clean.f32_dot == 0
    assert clean.f32_convert == 1  # only the bf16->f32 direction
    assert clean.host_calls == 0
    hot = parse_stablehlo(_STABLE_F32_DOT)
    assert hot.f32_dot == 1
    host = parse_stablehlo(_STABLE_HOST)
    assert host.host_calls == 2
    assert "xla_python_cpu_callback" in host.host_targets
    assert "infeed" in host.host_targets


# ------------------------------------------------------------ rule families
def test_tlh101_alias_present_vs_absent():
    ok = check_donation("p", parse_alias_count(_HLO_ALIASED), donated=2)
    assert ok == []
    bad = check_donation("p", parse_alias_count(_HLO_NO_ALIAS), donated=2)
    assert [f.rule for f in bad] == ["TLH101"]
    assert "0/2" in bad[0].message
    # pinned drift is its own fingerprint (distinguishable in baselines)
    drift = check_donation("p", 2, donated=2, pinned=3)
    assert [f.symbol for f in drift] == ["drift"]


def test_tlh102_oversized_all_gather():
    measured = parse_hlo(_HLO_COLLECTIVES).collective_bytes()
    cap = {"all-gather": measured["all-gather"], "all-reduce": 32}
    assert check_collectives("p", measured, cap) == []
    tight = {"all-gather": measured["all-gather"] - 1, "all-reduce": 32}
    over = check_collectives("p", measured, tight)
    assert [f.symbol for f in over] == ["over:all-gather"]
    # a kind with no budget at all is a finding even at tiny sizes
    new = check_collectives("p", measured, {"all-gather": 10**9})
    assert [f.symbol for f in new] == ["new:all-reduce"]
    # None budget = "no collectives allowed"
    assert len(check_collectives("p", measured, None)) == 2


def test_tlh103_f32_dot_in_bf16_program():
    stats = parse_stablehlo(_STABLE_F32_DOT)
    fs = check_dtype("p", "bfloat16", stats, max_f32_convert=1)
    assert [f.symbol for f in fs] == ["f32_dot"]
    # an f32 program may dot in f32 all it likes
    assert check_dtype("p", "float32", stats) == []
    # convert growth is the other half of the family
    grown = StableStats(f32_dot=0, f32_convert=5, host_calls=0)
    fs = check_dtype("p", "bfloat16", grown, max_f32_convert=4)
    assert [f.symbol for f in fs] == ["f32_convert"]


def test_tlh104_host_calls():
    fs = check_host_calls("p", parse_stablehlo(_STABLE_HOST))
    assert [f.rule for f in fs] == ["TLH104"]
    assert "xla_python_cpu_callback" in fs[0].message
    assert check_host_calls("p", parse_stablehlo(_STABLE_BF16_CLEAN)) == []


def test_tlh106_tolerance_edges():
    pinned = {"temp_bytes": 1000, "argument_bytes": 500}
    # exactly AT the tolerance boundary is allowed (strictly-greater)
    at = {"temp_bytes": 1100, "argument_bytes": 450}
    assert check_memory("p", at, pinned, tolerance=0.10) == []
    over = {"temp_bytes": 1101, "argument_bytes": 500}
    fs = check_memory("p", over, pinned, tolerance=0.10)
    assert [f.symbol for f in fs] == ["temp_bytes"]
    assert "+10.1%" in fs[0].message
    # shrinkage beyond tolerance is drift too — bank it by regenerating
    shrunk = {"temp_bytes": 880, "argument_bytes": 500}
    fs = check_memory("p", shrunk, pinned, tolerance=0.10)
    assert [f.symbol for f in fs] == ["temp_bytes"]
    # a ZERO pin still guards growth (relative tolerance is meaningless
    # at 0 and must not disable the rule for that program)
    zero = {"temp_bytes": 0, "argument_bytes": 500}
    assert check_memory("p", zero, {"temp_bytes": 0}, 0.10) == []
    fs = check_memory("p", {"temp_bytes": 7}, {"temp_bytes": 0}, 0.10)
    assert [f.symbol for f in fs] == ["temp_bytes"]


# ----------------------------------------------- real programs, end to end
def _audit_pair():
    """Two tiny REAL programs through the full lower->compile->parse."""

    def f(state):
        return {"x": state["x"] + 1, "y": state["y"] * 2}

    state = {"x": jnp.zeros((16,)), "y": jnp.zeros((16,))}
    a = audit_lowered(
        "toy.donating", jax.jit(f, donate_argnums=(0,)).lower(state),
        group="toy", donated=2,
    )
    b = audit_lowered(
        "toy.plain", jax.jit(f).lower(state), group="toy", donated=0,
    )
    return a, b


def test_broken_donation_caught_by_tlh101():
    """The acceptance scenario: the same program with donate_argnums
    dropped (the scratch-copy regression) must be caught by TLH101."""
    donating, plain = _audit_pair()
    assert donating.alias == donating.donated == 2
    assert check_donation(
        donating.name, donating.alias, donating.donated
    ) == []
    # "broken" = the donation annotation was lost but the audit still
    # EXPECTS the buffers to alias — exactly what the enumeration hooks
    # declare for the serving/trainer state
    fs = check_donation(plain.name, plain.alias, donated=2)
    assert [f.rule for f in fs] == ["TLH101"]
    assert "0/2" in fs[0].message


def test_partially_dropped_donation_caught():
    """A donated leaf that falls out of the output tree loses its alias
    pair while the rest keep theirs — the per-leaf silent-copy case."""

    def f(state):
        return {"x": state["x"] + 1}  # y donated but never aliased

    state = {"x": jnp.zeros((16,)), "y": jnp.zeros((16,))}
    a = audit_lowered(
        "toy.partial", jax.jit(f, donate_argnums=(0,)).lower(state),
        donated=2,
    )
    assert a.alias < 2
    fs = check_donation(a.name, a.alias, a.donated)
    assert [f.rule for f in fs] == ["TLH101"]


def test_manifest_roundtrip_and_drift(tmp_path):
    donating, plain = _audit_pair()
    path = str(tmp_path / MANIFEST_NAME)
    write_manifest(path, [donating, plain])
    man = load_manifest(path)
    assert set(man["programs"]) == {"toy.donating", "toy.plain"}
    assert man["programs"]["toy.donating"]["alias"] == 2

    # clean against its own pins
    assert audit_findings([donating, plain], man) == []

    # tampered pins surface as the right families
    man["programs"]["toy.donating"]["alias"] = 3
    man["programs"]["toy.plain"]["temp_bytes"] = max(
        plain.temp_bytes * 2, 64
    )
    fs = audit_findings([donating, plain], man)
    assert {(f.rule, f.path) for f in fs} == {
        ("TLH101", "toy.donating"), ("TLH106", "toy.plain"),
    }

    # a pinned program that stops enumerating + the group count (TLH105)
    man = load_manifest(path)
    man["programs"]["toy.ghost"] = dict(
        man["programs"]["toy.plain"], group="toy"
    )
    fs = audit_findings([donating, plain], man)
    assert {f.symbol for f in fs} == {"missing", "count"}
    assert all(f.rule == "TLH105" for f in fs)
    # ...unless the selector excluded it (a narrowed --only run)
    fs = audit_findings(
        [donating, plain], man, selected=lambda n: n != "toy.ghost"
    )
    assert fs == []

    # a NEW program not yet pinned
    man = load_manifest(path)
    third = ProgramAudit(
        name="toy.new", group="toy", dtype="float32", donated=0,
        ir=parse_hlo(_HLO_NO_ALIAS), stable=parse_stablehlo(""),
        temp_bytes=0, argument_bytes=0, output_bytes=0,
    )
    fs = audit_findings([donating, plain, third], man)
    assert {f.symbol for f in fs} == {"unpinned", "count"}


def test_no_manifest_runs_live_rules_only():
    """--manifest none semantics: the pin-relative families (collective
    budgets, convert counts, memory, program sets) stay quiet — a
    pristine tree must exit clean — while the live invariants (donation
    coverage, zero f32 dots, host calls) still fire."""
    ir = parse_hlo(_HLO_COLLECTIVES)  # carries all-gather + all-reduce
    ok = ProgramAudit(
        name="g.ok", group="g", dtype="bfloat16", donated=0, ir=ir,
        stable=StableStats(f32_dot=0, f32_convert=24, host_calls=0),
        temp_bytes=10, argument_bytes=10, output_bytes=10,
    )
    assert audit_findings([ok], None) == []
    bad = ProgramAudit(
        name="g.bad", group="g", dtype="bfloat16", donated=3, ir=ir,
        stable=StableStats(f32_dot=2, f32_convert=0, host_calls=0),
        temp_bytes=10, argument_bytes=10, output_bytes=10,
    )
    fs = audit_findings([bad], None)
    assert sorted(f.symbol for f in fs) == ["dropped", "f32_dot"]


def test_write_manifest_preserves_suppress_reasons(tmp_path):
    donating, plain = _audit_pair()
    path = str(tmp_path / MANIFEST_NAME)
    with open(path, "w") as fh:
        json.dump({
            "programs": {},
            "suppress": [{
                "fingerprint": "TLH104:toy.donating:host",
                "reason": "sanctioned logging tap",
            }],
        }, fh)
    write_manifest(path, [donating, plain])
    man = load_manifest(path)
    assert man["suppress"] == [{
        "fingerprint": "TLH104:toy.donating:host",
        "reason": "sanctioned logging tap",
    }]
    # and re-pinning keeps programs a narrowed run did not re-audit
    write_manifest(path, [donating])
    assert set(load_manifest(path)["programs"]) == {
        "toy.donating", "toy.plain",
    }


def test_github_format_annotation_shape():
    fs = check_donation("continuous.decode", 0, donated=12)
    out = render_findings(fs, "github")
    line = out.splitlines()[0]
    assert re.fullmatch(
        r"::error file=continuous\.decode,line=1,"
        r"title=tlhlo TLH101::[^\r\n]+",
        line,
    )
    # newlines/percents must be escaped into the single-line grammar
    from tensorlink_tpu.analysis.core import Finding

    tricky = Finding("TLH104", "p", 1, "a%b\nc", symbol="host")
    out = render_findings([tricky], "github")
    assert "a%25b%0Ac" in out
    assert "\n" not in out.splitlines()[0][1:]


def test_json_format_carries_explanations():
    fs = check_donation("p", 0, donated=1)
    data = json.loads(render_findings(fs, "json", {"suppressed": 0}))
    assert data["suppressed"] == 0
    f = data["findings"][0]
    assert f["rule"] == "TLH101"
    assert f["fingerprint"] == "TLH101:p:dropped"
    assert f["explanation"] == HLO_RULES["TLH101"].strip().splitlines()[0]


def test_masked_k_change_does_not_grow_program_set():
    """ISSUE-12 / TLH105 regression gate: per-request K is a TRACED
    operand of the one spec-chunk program, so an adaptive engine under
    K churn must present EXACTLY the program set the committed
    manifest pins for its group — same names, same count, and zero
    fresh jit traces after the churn. A masked-K implementation that
    specialized per K (static argnum, shape, or a sibling program)
    fails here before it fails in production retrace storms."""
    import numpy as np

    from tensorlink_tpu.config import MeshConfig
    from tensorlink_tpu.models.llama import Llama, LlamaConfig
    from tensorlink_tpu.parallel.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from tensorlink_tpu.parallel.serving import (
        ContinuousBatchingEngine,
        SpecConfig,
    )
    from tensorlink_tpu.runtime.mesh import make_mesh

    cfg = LlamaConfig.tiny()
    m = Llama(cfg)
    eng = InferenceEngine(
        make_mesh(MeshConfig()), m, m.init(jax.random.key(0)), max_len=32,
        cache_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    sch = ContinuousBatchingEngine(
        eng, slots=2, gen=GenerationConfig(max_new_tokens=6),
        decode_chunk=2, prefill_block=16,
        speculative=SpecConfig(k=2, rounds=1, adaptive=True),
    )
    path = find_default_manifest(os.path.dirname(__file__))
    assert path is not None
    man_names = {
        n.split(".", 1)[1]
        for n in load_manifest(path).get("programs", {})
        if n.startswith("continuous.") and "spec" in n
    }
    assert {p["name"] for p in sch.audit_programs()} == man_names
    # drive per-request K churn: rejection-heavy traffic (n-gram over
    # random tiny-model output) walks K down per request while fresh
    # requests start at the prior
    r = np.random.default_rng(5)
    for n in (6, 9, 4, 7):
        sch.submit(r.integers(0, cfg.vocab_size, (n,)))
    sch.run_until_idle()
    ks = {sch._kctl.k_for_acceptance(a / 10) for a in range(10)}
    assert len(ks) > 1  # the controller genuinely varies K
    assert {p["name"] for p in sch.audit_programs()} == man_names
    if hasattr(sch._decode, "_cache_size"):
        assert sch._decode._cache_size() == 1  # ONE spec program, still


# -------------------------------------------------- canonical enumeration
@pytest.fixture(scope="module")
def canonical_audit():
    """ONE full canonical audit shared by the integration tests (it
    compiles ~10 programs; everything below reads the same result)."""
    return run_audit()


def test_canonical_audit_covers_the_fleet(canonical_audit):
    audits, skipped = canonical_audit
    names = {a.name for a in audits}
    # the acceptance floor: both serving engines' decode/prefill/spec
    # plus the trainer step, >= 8 programs total
    assert len(audits) >= 8
    assert {
        "continuous.decode", "continuous.prefill_b16",
        "continuous.spec_chunk", "continuous.prefill_b16_spec",
        "paged.decode", "paged.prefill_chunk", "paged.spec_chunk",
        "paged.prefill_chunk_spec", "trainer.step",
    } <= names
    # nothing vanishes: a canonical program that fails to build or
    # lower is reported as skipped, and the only skip this suite lets
    # pass is a mesh the process is too small for (conftest gives 8
    # devices, so here that means none)
    broken = [
        (n, why) for n, why in skipped
        if not why.startswith("TooFewDevices")
    ]
    assert not broken, broken
    too_small = {n for n, _ in skipped} - {n for n, _ in broken}
    for want in ("sharded.step", "paged_kernel.decode",
                 "infer.kv_shard_decode"):
        assert want in names or want.split(".")[0] in too_small, want


def test_canonical_audit_clean_on_committed_manifest(canonical_audit):
    audits, skipped = canonical_audit
    path = find_default_manifest(os.path.dirname(__file__))
    assert path is not None, f"committed {MANIFEST_NAME} not found"
    man = load_manifest(path)

    def selected(name):
        return not any(
            name == n or name.startswith(n + ".") for n, _ in skipped
        )

    findings = audit_findings(audits, man, selected=selected)
    suppressed = {
        e["fingerprint"] if isinstance(e, dict) else e
        for e in man.get("suppress", [])
    }
    fresh = [f for f in findings if f.fingerprint not in suppressed]
    assert not fresh, "\n".join(str(f) for f in fresh)


def test_canonical_donations_all_honored(canonical_audit):
    """TLH101 ground truth for the real engines: every donated serving/
    trainer state leaf survived to an input/output alias pair. This is
    the invariant that keeps the KV cache updating in place."""
    audits, _ = canonical_audit
    for a in audits:
        if a.donated:
            assert a.alias == a.donated, (
                f"{a.name}: {a.alias}/{a.donated} aliased"
            )


def test_canonical_bf16_programs_have_no_f32_dot(canonical_audit):
    """TLH103 ground truth: no serving/trainer matmul silently left the
    bf16 path (counted on pre-backend StableHLO — CPU legalization
    would make the optimized HLO all-f32 and prove nothing)."""
    audits, _ = canonical_audit
    checked = 0
    for a in audits:
        if a.dtype == "bfloat16":
            if a.group == "paged_kernel":
                # the interpret-mode lowering inlines the Pallas
                # kernel's f32 online-softmax accumulator as visible
                # f32 dots (by design — on TPU they live inside the
                # fused custom call); TLH103 pins the exact count in
                # the manifest instead
                assert a.stable.f32_dot > 0, a.name
                continue
            assert a.stable.f32_dot == 0, a.name
            checked += 1
    assert checked >= 7
