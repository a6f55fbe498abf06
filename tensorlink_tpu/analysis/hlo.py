"""tlhlo — static analysis over the framework's COMPILED programs.

tlint (the sibling checkers in this package) audits the Python source;
this module audits what XLA actually produced. The invariants that
decide whether serving/training run "as fast as the hardware allows"
live in the compiled artifact, not the source: whether a
``donate_argnums`` survived to an input/output alias (a dropped
donation is a silent 2x HBM copy of the KV cache every chunk), whether
the partitioner gathers a sharded cache, whether a bf16 hot path
silently upcasts to f32, whether a host callback snuck into a jitted
body. Each of those used to be a one-off ``as_text()`` grep in a
single test; here they are rule families over a small parsed IR, run
against every load-bearing program the framework compiles and pinned
by a committed ``hlo.manifest.json`` (same baseline discipline as
tlint: accepted findings carry ``{fingerprint, reason}`` entries).

Two texts are parsed per program, deliberately:

- ``lowered.as_text()`` (StableHLO, pre-backend): dtype discipline.
  Backend legalization rewrites dtypes — XLA:CPU turns every bf16 dot
  into convert→f32 dot→convert — so only the pre-backend text says
  what the PROGRAM asked for, platform-independently.
- ``compiled.as_text()`` (optimized HLO): input/output aliasing,
  collectives, host transfers — partitioner and buffer-assignment
  facts that only exist after compilation — plus
  ``memory_analysis()``/``cost_analysis()``.

Known limit (documented in README): the canonical enumeration lowers
on CPU (``lower()`` needs only avals, so multi-GB donated state costs
nothing), which pins SPMD partitioning, aliasing, and program
structure exactly, but temp-byte numbers and fusion choices are the
CPU backend's — on-device TPU HLO differs in scheduling, not in the
invariants audited here.

CLI: ``tlhlo`` / ``python -m tensorlink_tpu.analysis.hlo``.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

from tensorlink_tpu.analysis.core import (
    Finding,
    github_annotation,
    load_baseline_reasons,
    register_rules,
)

MANIFEST_NAME = "hlo.manifest.json"

HLO_RULES = {
    "TLH101": (
        "Donation dropped: a donate_argnums buffer did not survive to an "
        "input/output alias in the compiled program.\n\n"
        "jax.jit(fn, donate_argnums=...) is a REQUEST; XLA honors it by "
        "recording an input_output_alias pair per donated buffer. A "
        "donated leaf that is read after its aliased output is written, "
        "changes dtype/shape, or is simply dropped from the output tree "
        "compiles fine — it just silently costs a full extra copy of the "
        "buffer (for a serving KV cache, 2x HBM every dispatched chunk). "
        "The rule compares aliased pairs against the donated arg's leaf "
        "count, and against the count pinned in hlo.manifest.json."
    ),
    "TLH102": (
        "Collective budget exceeded: an all-gather/all-reduce/"
        "reduce-scatter/all-to-all result outgrew the manifest bound.\n\n"
        "Per program, the largest collective RESULT in bytes per kind is "
        "pinned in the manifest. Growth means the partitioner started "
        "materializing something it used to keep sharded (the classic "
        "failure: gathering the KV cache turns sequence-sharded serving "
        "into replicated serving plus collectives). A kind absent from "
        "the manifest appearing at all is the same finding."
    ),
    "TLH103": (
        "Dtype discipline: an f32 dot/convolution (or a new bf16->f32 "
        "convert) appeared in a program declared bf16/int8.\n\n"
        "Counted on the PRE-BACKEND StableHLO (backend legalization on "
        "CPU rewrites every bf16 matmul through f32, which is not the "
        "program's fault). Some f32 is deliberate — softmax, sampling, "
        "loss — so the manifest pins the expected counts; the finding is "
        "the count GROWING, i.e. a matmul or cast chain that silently "
        "left the low-precision path."
    ),
    "TLH104": (
        "Host round-trip inside a jitted body: infeed/outfeed/send/recv "
        "or a host-callback custom-call.\n\n"
        "A host transfer inside a hot program serializes the device on "
        "the Python runtime every dispatch. jax.debug.callback/"
        "io_callback/pure_callback lower to custom-calls "
        "(*_python_cpu_callback); debug prints left in a decode chunk or "
        "train step are exactly this. Deliberate ones (a sanctioned "
        "logging tap) belong in the manifest suppress list with a "
        "reason."
    ),
    "TLH105": (
        "Program-count budget: the set of compiled programs per engine "
        "drifted from the manifest.\n\n"
        "The serving engines' contract is ONE decode + ONE prefill (+ "
        "ONE spec) program serving any request mix — an accidental "
        "second decode program means some code path retraces per "
        "request shape. The manifest records the enumerated program "
        "names; a new name, a missing name, or a changed per-group "
        "count is the finding.\n\n"
        "Pipeline-sharded serving extends the same contract per stage: "
        "each stage engine compiles exactly ONE decode + ONE "
        "prefill_chunk program over its own layer span, so the "
        "pipeline group's total budget scales with stage count only — "
        "never with the request mix crossing the activation wire."
    ),
    "TLH106": (
        "Memory budget: temp or argument bytes moved beyond the "
        "manifest tolerance.\n\n"
        "memory_analysis() of the compiled program gives XLA's own "
        "accounting of scratch (temp) and input (argument) bytes. Temp "
        "growth is a regression in rematerialization/fusion (or a lost "
        "donation showing up as a scratch copy); argument growth means "
        "the program's operand tree grew. Compared within --tolerance "
        "(default 10%) in BOTH directions — shrinkage is drift too, and "
        "should be banked by regenerating the manifest."
    ),
}
register_rules(HLO_RULES)

# element-type widths for HLO/StableHLO shape strings
_ELEM_BYTES = {
    "pred": 1, "i1": 1, "s8": 1, "u8": 1, "i8": 1,
    "s16": 2, "u16": 2, "i16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "i32": 4, "f32": 4,
    "s64": 8, "u64": 8, "i64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

# one optimized-HLO instruction: `%name = <type>[dims]{layout} op(...)`
# (tuple results open with '('; the FIRST element type is captured; a
# tuple of more than five elements carries `/*index=5*/` marks)
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s+=\s+\(?\(?\s*"
    r"([a-z][a-z0-9]*)\[([0-9,]*)\]"     # result element type + dims
    r"(?:[^=]|/\*index=\d+\*/)*?"
    r"\s([a-z][a-z0-9\-]*)\("            # op mnemonic
)

# StableHLO: dot/convolution result types and convert signatures
_ST_DOT_RE = re.compile(
    r"stablehlo\.(?:dot_general|dot|convolution)\b[^\n]*?"
    r"->\s*tensor<([^>]*)>"
)
_ST_CONVERT_RE = re.compile(
    r"stablehlo\.convert\b[^\n]*?:\s*\(?tensor<([^>]*)>\)?"
    r"\s*->\s*tensor<([^>]*)>"
)
_ST_HOST_RE = re.compile(
    r"stablehlo\.(infeed|outfeed|send|recv)\b"
    r"|stablehlo\.custom_call\s+@([\w.\-]*(?:callback|host|Host)[\w.\-]*)"
)


def _tensor_elem(spec: str) -> str:
    """'2x32xbf16' -> 'bf16'; 'f32' (scalar) -> 'f32'."""
    return spec.rsplit("x", 1)[-1].split(",")[0].strip()


@dataclass(frozen=True)
class HloOp:
    """One parsed instruction: mnemonic + (first) result type."""

    kind: str
    dtype: str
    shape: tuple[int, ...]

    @property
    def elems(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        return self.elems * _ELEM_BYTES.get(self.dtype, 4)


@dataclass
class HloIR:
    """Parsed optimized-HLO program: instruction list + alias count.

    Every tensor in the program is some instruction's RESULT (parameters
    included — they are ``parameter(n)`` instructions), so result-level
    queries cover operands too.
    """

    ops: list[HloOp]
    alias: int

    def count(self, kind: str, dtype: str | None = None,
              shape: tuple[int, ...] | None = None) -> int:
        """Instructions of ``kind`` (collective -start forms fold into
        their base kind), optionally filtered by result dtype/shape."""
        n = 0
        for op in self.ops:
            k = op.kind[:-6] if op.kind.endswith("-start") else op.kind
            if k != kind:
                continue
            if dtype is not None and op.dtype != dtype:
                continue
            if shape is not None and op.shape != tuple(shape):
                continue
            n += 1
        return n

    def has_result(self, dtype: str, shape: tuple[int, ...]) -> bool:
        """Does ANY instruction produce this exact type? (The
        "full-width cache must not exist" style of pin.)"""
        shape = tuple(shape)
        return any(
            op.dtype == dtype and op.shape == shape for op in self.ops
        )

    def collectives(self) -> list[HloOp]:
        """Collective instructions (-start folded in, -done dropped:
        the done op re-reports the started transfer's buffer)."""
        out = []
        for op in self.ops:
            k = op.kind[:-6] if op.kind.endswith("-start") else op.kind
            if k in COLLECTIVE_KINDS and not op.kind.endswith("-done"):
                out.append(HloOp(k, op.dtype, op.shape))
        return out

    def collective_bytes(self) -> dict[str, int]:
        """kind -> largest collective RESULT in bytes. Result bytes is
        the materialized-tensor metric: for an all-gather it is the
        gathered (full) tensor — exactly what a cache-gather regression
        inflates."""
        out: dict[str, int] = {}
        for op in self.collectives():
            out[op.kind] = max(out.get(op.kind, 0), op.bytes)
        return out


def parse_alias_count(text: str) -> int:
    """Number of input/output alias pairs in an optimized-HLO module
    header: ``input_output_alias={ {0}: (21, {}, may-alias), ... }``."""
    i = text.find("input_output_alias={")
    if i < 0:
        return 0
    # balanced-brace scan (entries nest one level of {} each)
    depth = 0
    j = text.index("{", i)
    for k in range(j, min(len(text), j + 200_000)):
        if text[k] == "{":
            depth += 1
        elif text[k] == "}":
            depth -= 1
            if depth == 0:
                seg = text[j:k + 1]
                return len(re.findall(r"\{[\d,\s]*\}\s*:\s*\(\d+", seg))
    return 0


_TYPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")


def parse_hlo(text: str) -> HloIR:
    """Optimized HLO text -> :class:`HloIR`."""
    ops: list[HloOp] = []
    for line in text.splitlines():
        m = _HLO_OP_RE.match(line)
        if not m:
            continue
        kind = m.group(3)
        dtype = m.group(1)
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        base = kind[:-6] if kind.endswith("-start") else kind
        if base in COLLECTIVE_KINDS and f"= ({dtype}[" in line:
            # tuple-result collectives: async -start ops put the
            # PRE-collective input shard first, and XLA's combiner
            # merges gradient all-reduces into variadic (tuple) sync
            # ops whose first element may be the smallest operand.
            # Recording the first element would under-measure the
            # budget; take the LARGEST tuple element — the biggest
            # tensor the collective materializes (the matching -done
            # op is dropped later).
            head = line[:line.find(f" {kind}(")]
            best = (0, dtype, dims)
            for dt, ds in _TYPE_RE.findall(head):
                sh = tuple(int(d) for d in ds.split(",") if d)
                n = _ELEM_BYTES.get(dt, 4)
                for d in sh:
                    n *= d
                best = max(best, (n, dt, sh))
            _, dtype, dims = best
        ops.append(HloOp(kind, dtype, dims))
    return HloIR(ops=ops, alias=parse_alias_count(text))


@dataclass
class StableStats:
    """Dtype-discipline counts from the pre-backend StableHLO text."""

    f32_dot: int  # dot_general/convolution producing f32
    f32_convert: int  # bf16/f16 -> f32 converts (the upcast chains)
    host_calls: int
    host_targets: list[str] = field(default_factory=list)


def parse_stablehlo(text: str) -> StableStats:
    f32_dot = sum(
        1 for m in _ST_DOT_RE.finditer(text)
        if _tensor_elem(m.group(1)) == "f32"
    )
    f32_convert = sum(
        1 for m in _ST_CONVERT_RE.finditer(text)
        if _tensor_elem(m.group(1)) in ("bf16", "f16")
        and _tensor_elem(m.group(2)) == "f32"
    )
    targets = []
    for m in _ST_HOST_RE.finditer(text):
        targets.append(m.group(1) or m.group(2))
    return StableStats(
        f32_dot=f32_dot, f32_convert=f32_convert,
        host_calls=len(targets), host_targets=targets,
    )


# ---------------------------------------------------------------- audits
@dataclass
class ProgramAudit:
    """Everything the rules need to know about one compiled program."""

    name: str
    group: str
    dtype: str        # declared hot-path compute dtype
    donated: int      # donated leaves the aliasing must cover
    ir: HloIR
    stable: StableStats
    temp_bytes: int
    argument_bytes: int
    output_bytes: int
    flops: float | None = None

    @property
    def alias(self) -> int:
        return self.ir.alias

    def record(self) -> dict:
        """The manifest entry this audit pins."""
        return {
            "group": self.group,
            "dtype": self.dtype,
            "donated": self.donated,
            "alias": self.alias,
            "collectives": self.ir.collective_bytes(),
            "f32_dot": self.stable.f32_dot,
            "f32_convert": self.stable.f32_convert,
            "host_calls": self.stable.host_calls,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
        }


def audit_lowered(name: str, lowered, *, group: str = "",
                  dtype: str = "float32", donated: int = 0) -> ProgramAudit:
    """Lower -> compile -> parse one program into a :class:`ProgramAudit`.

    ``lowered`` is a ``jax.stages.Lowered`` (from ``jitfn.lower(...)`` —
    avals suffice, donated state buffers are never touched)."""
    stable = parse_stablehlo(lowered.as_text())
    compiled = lowered.compile()
    ir = parse_hlo(compiled.as_text())
    temp = arg = out = 0
    try:
        mem = compiled.memory_analysis()
        temp = int(mem.temp_size_in_bytes)
        arg = int(mem.argument_size_in_bytes)
        out = int(mem.output_size_in_bytes)
    except Exception:  # noqa: BLE001 — not every backend reports memory
        pass
    flops = None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost["flops"])
    except Exception:  # noqa: BLE001 — advisory only
        pass
    return ProgramAudit(
        name=name, group=group, dtype=dtype, donated=donated, ir=ir,
        stable=stable, temp_bytes=temp, argument_bytes=arg,
        output_bytes=out, flops=flops,
    )


# ----------------------------------------------------------------- rules
# Rule helpers are standalone so tests can invoke them declaratively on
# their own compiled programs (the migrated kv-shard / MoE pins) — the
# auditor below composes the same functions against the manifest.
def check_donation(
    name: str, alias: int, donated: int, pinned: int | None = None
) -> list[Finding]:
    """TLH101: every donated leaf must survive to an alias pair."""
    out = []
    if donated and alias < donated:
        out.append(Finding(
            "TLH101", name, 1,
            f"donation dropped: {alias}/{donated} donated leaves aliased "
            f"in the compiled program",
            symbol="dropped",
        ))
    if pinned is not None and alias != pinned:
        out.append(Finding(
            "TLH101", name, 1,
            f"alias drift: {alias} input/output alias pairs vs {pinned} "
            f"pinned in the manifest",
            symbol="drift",
        ))
    return out


def check_collectives(
    name: str, measured: dict[str, int],
    budgets: dict[str, int] | None,
) -> list[Finding]:
    """TLH102: per-kind largest collective result vs the pinned bound.
    ``budgets=None`` means "no collectives allowed at all"."""
    out = []
    for kind, nbytes in sorted(measured.items()):
        cap = (budgets or {}).get(kind)
        if cap is None:
            out.append(Finding(
                "TLH102", name, 1,
                f"new collective: {kind} of {nbytes} bytes, no budget "
                f"in the manifest",
                symbol=f"new:{kind}",
            ))
        elif nbytes > cap:
            out.append(Finding(
                "TLH102", name, 1,
                f"{kind} result grew to {nbytes} bytes "
                f"(budget {cap}): the partitioner is materializing "
                f"something it used to keep sharded",
                symbol=f"over:{kind}",
            ))
    return out


def check_dtype(
    name: str, declared: str, stats: StableStats,
    max_f32_dot: int = 0, max_f32_convert: int = 0,
) -> list[Finding]:
    """TLH103: f32 math appearing in a low-precision program."""
    if declared not in ("bfloat16", "float16", "int8"):
        return []
    out = []
    if stats.f32_dot > max_f32_dot:
        out.append(Finding(
            "TLH103", name, 1,
            f"{stats.f32_dot} f32 dot/convolution(s) in a {declared} "
            f"program (manifest allows {max_f32_dot}): a matmul left "
            f"the low-precision path",
            symbol="f32_dot",
        ))
    if stats.f32_convert > max_f32_convert:
        out.append(Finding(
            "TLH103", name, 1,
            f"{stats.f32_convert} bf16/f16->f32 convert(s) in a "
            f"{declared} program (manifest allows {max_f32_convert}): "
            f"an upcast chain grew",
            symbol="f32_convert",
        ))
    return out


def check_host_calls(name: str, stats: StableStats) -> list[Finding]:
    """TLH104: host transfers inside the jitted body."""
    if not stats.host_calls:
        return []
    shown = ", ".join(sorted(set(stats.host_targets))[:4])
    return [Finding(
        "TLH104", name, 1,
        f"{stats.host_calls} host round-trip(s) inside the jitted body "
        f"({shown}): the device serializes on Python every dispatch",
        symbol="host",
    )]


def check_memory(
    name: str, measured: dict[str, int], pinned: dict,
    tolerance: float,
) -> list[Finding]:
    """TLH106: temp/argument bytes vs manifest, both directions."""
    out = []
    for key in ("temp_bytes", "argument_bytes"):
        want = pinned.get(key)
        got = measured.get(key, 0)
        if not isinstance(want, (int, float)):
            continue
        if want <= 0:
            # a zero pin (trivial program, or a backend that could not
            # report memory when the manifest was written) still guards
            # GROWTH — relative tolerance has no meaning at 0, and
            # skipping would disable the rule for that program forever
            if got > 0:
                out.append(Finding(
                    "TLH106", name, 1,
                    f"{key} {got} vs 0 pinned (tolerance does not "
                    f"apply to a zero pin — re-pin after review)",
                    symbol=key,
                ))
            continue
        if abs(got - want) > tolerance * want:
            out.append(Finding(
                "TLH106", name, 1,
                f"{key} {got} vs {want} pinned "
                f"({(got - want) / want:+.1%}, tolerance "
                f"{tolerance:.0%})",
                symbol=key,
            ))
    return out


def audit_findings(
    audits: list[ProgramAudit],
    manifest: dict | None,
    tolerance: float = 0.10,
    selected: Callable[[str], bool] | None = None,
) -> list[Finding]:
    """Run every rule family over the audited programs vs the manifest.

    ``manifest=None`` runs only the LIVE rules — the invariants that
    hold without any pin: donation coverage (TLH101), zero f32
    dots in low-precision programs (TLH103), no host round-trips
    (TLH104). Pin-relative checks (collective budgets, convert counts,
    memory, program sets) need a manifest and are skipped, so a
    pristine tree exits clean either way.

    ``selected`` mirrors the CLI's --only/--skip: manifest programs it
    rejects are not reported missing (a narrowed run must not claim the
    rest of the manifest drifted)."""
    programs = (manifest or {}).get("programs", {})
    findings: list[Finding] = []
    seen_groups: dict[str, int] = {}
    pinned_groups: dict[str, int] = {}
    for name, rec in programs.items():
        if selected is None or selected(name):
            g = rec.get("group", "")
            pinned_groups[g] = pinned_groups.get(g, 0) + 1

    for a in audits:
        seen_groups[a.group] = seen_groups.get(a.group, 0) + 1
        rec = programs.get(a.name)
        if rec is None:
            if manifest is not None:
                findings.append(Finding(
                    "TLH105", a.name, 1,
                    "program not in the manifest: a new compiled program "
                    "appeared (regenerate with --write-manifest after "
                    "review)",
                    symbol="unpinned",
                ))
            rec = {}
        findings.extend(check_donation(
            a.name, a.alias, a.donated, rec.get("alias"),
        ))
        if manifest is not None:
            findings.extend(check_collectives(
                a.name, a.ir.collective_bytes(),
                rec.get("collectives") if rec else None,
            ))
        findings.extend(check_dtype(
            a.name, a.dtype, a.stable,
            int(rec.get("f32_dot", 0)),
            # deliberate f32 convert islands (softmax/sampling/norms)
            # only exist as pinned counts — unbounded without pins
            int(rec.get("f32_convert", 0)) if manifest is not None
            else a.stable.f32_convert,
        ))
        if a.stable.host_calls > int(rec.get("host_calls", 0)):
            findings.extend(check_host_calls(a.name, a.stable))
        if rec:
            findings.extend(check_memory(
                a.name, {
                    "temp_bytes": a.temp_bytes,
                    "argument_bytes": a.argument_bytes,
                }, rec, tolerance,
            ))

    measured_names = {a.name for a in audits}
    for name, rec in programs.items():
        if name in measured_names:
            continue
        if selected is not None and not selected(name):
            continue
        findings.append(Finding(
            "TLH105", name, 1,
            "program pinned in the manifest was not enumerated: it was "
            "removed or its engine stopped exposing it",
            symbol="missing",
        ))
    for g, n in sorted(seen_groups.items()):
        want = pinned_groups.get(g)
        if manifest is not None and want is not None and n != want:
            findings.append(Finding(
                "TLH105", g, 1,
                f"engine group {g!r} compiles {n} program(s), manifest "
                f"pins {want} (ONE decode + ONE prefill + ONE spec is "
                f"the serving contract)",
                symbol="count",
            ))
    findings.sort(key=lambda f: (f.path, f.rule, f.symbol))
    return findings


# -------------------------------------------------------------- manifest
def load_manifest(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    if not isinstance(data, dict) or "programs" not in data:
        raise ValueError(f"{path}: not a tlhlo manifest (no 'programs')")
    return data


def write_manifest(
    path: str, audits: list[ProgramAudit],
    skipped: list[tuple[str, str]] = (),
) -> None:
    """Pin the audited programs, PRESERVING the suppress list (and its
    reasons) plus pinned entries for programs this run skipped — a
    narrowed or degraded-environment run must not silently unpin the
    rest of the fleet."""
    old_programs: dict = {}
    reasons: dict[str, str] = {}
    if os.path.exists(path):
        try:
            old = load_manifest(path)
            old_programs = old.get("programs", {})
            reasons = load_baseline_reasons(path)
        except (OSError, ValueError):
            pass
    programs = dict(old_programs)
    for a in audits:
        programs[a.name] = a.record()
    data = {
        "comment": (
            "Compiled-program manifest; `tlhlo` fails on drift from "
            "these pins. Regenerate with --write-manifest after "
            "reviewing what changed; accepted findings go in 'suppress' "
            "with a one-line reason."
        ),
        "programs": {k: programs[k] for k in sorted(programs)},
        "suppress": [
            {"fingerprint": fp, "reason": reasons[fp]}
            for fp in sorted(reasons)
        ],
    }
    if skipped:
        data["skipped"] = {name: why for name, why in sorted(skipped)}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=2)
        fh.write("\n")


def find_default_manifest(start: str = ".") -> str | None:
    cur = os.path.abspath(start)
    if not os.path.isdir(cur):
        cur = os.path.dirname(cur) or "."
    while True:
        cand = os.path.join(cur, MANIFEST_NAME)
        if os.path.exists(cand):
            return cand
        nxt = os.path.dirname(cur)
        if nxt == cur:
            return None
        cur = nxt


# ----------------------------------------------------- program enumeration
class TooFewDevices(RuntimeError):
    """A canonical group needs a larger mesh than this process has —
    the one skip reason that is the environment's and not a fault
    (``tests/test_hlo.py`` fails on any other)."""


def canonical_programs(
    only: list[str] | None = None, skip: list[str] | None = None,
) -> tuple[list[dict], list[tuple[str, str]]]:
    """Enumerate the framework's load-bearing compiled programs.

    Returns ``(programs, skipped)``: each program dict carries
    ``name``/``group``/``dtype``/``donated`` plus a ``lower`` thunk
    producing the ``jax.stages.Lowered``. Tiny models, real program
    BUILDERS: the jit closures lowered here are the same functions the
    production engines dispatch, so aliasing, program structure, and
    dtype flow are the real thing — only the weights are small.
    Engine families that this environment cannot trace are reported
    in ``skipped``, never silently dropped."""
    import jax
    import jax.numpy as jnp

    programs: list[dict] = []
    skipped: list[tuple[str, str]] = []

    def _add(group: str, items: list[dict]) -> None:
        for it in items:
            it["name"] = f"{group}.{it['name']}"
            it["group"] = group
            programs.append(it)

    def _try(group: str, build: Callable[[], list[dict]]) -> None:
        try:
            _add(group, build())
        except Exception as e:  # noqa: BLE001 — report, don't die
            skipped.append((group, f"{type(e).__name__}: {e}"))

    from tensorlink_tpu.config import MeshConfig, TrainConfig
    from tensorlink_tpu.models.llama import Llama, LlamaConfig
    from tensorlink_tpu.parallel.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from tensorlink_tpu.runtime.mesh import make_mesh

    key = jax.random.key(0)

    def serving_engines() -> list[dict]:
        from tensorlink_tpu.parallel.serving import (
            ContinuousBatchingEngine,
            PagedContinuousBatchingEngine,
            SpecConfig,
        )

        cfg = LlamaConfig.tiny()
        m = Llama(cfg)
        p = m.init(key)
        eng = InferenceEngine(
            make_mesh(MeshConfig()), m, p, max_len=64,
            cache_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        )
        out: list[dict] = []
        kw = dict(slots=2, decode_chunk=2, prefill_block=16)
        sc = SpecConfig(k=2, rounds=1)
        plain = ContinuousBatchingEngine(eng, **kw)
        spec = ContinuousBatchingEngine(eng, speculative=sc, **kw)
        for it in plain.audit_programs() + spec.audit_programs():
            it["name"] = f"continuous.{it['name']}"
            it["group"] = "continuous"
            if it["name"] not in {o["name"] for o in out}:
                out.append(it)
        pplain = PagedContinuousBatchingEngine(
            eng, block_size=8, prefill_chunk=16, **kw
        )
        pspec = PagedContinuousBatchingEngine(
            eng, block_size=8, prefill_chunk=16, speculative=sc, **kw
        )
        for it in pplain.audit_programs() + pspec.audit_programs():
            it["name"] = f"paged.{it['name']}"
            it["group"] = "paged"
            if it["name"] not in {o["name"] for o in out}:
                out.append(it)
        # int8 KV pools (ISSUE 20): same engine geometry, quantized
        # block form — still ONE decode + ONE prefill-chunk program
        # (TLH105), with the TLH106 temp/argument budgets pinned LOWER
        # (int8 blocks + f32 scales vs bf16) and the write-time
        # quantize / read-time dequantize converts under TLH103
        pint8 = PagedContinuousBatchingEngine(
            eng, block_size=8, prefill_chunk=16, kv_quant="int8", **kw
        )
        for it in pint8.audit_programs():
            it["name"] = f"paged_int8.{it['name']}"
            it["group"] = "paged_int8"
            out.append(it)
        # kernel-bearing decode (ISSUE 20 tentpole): the same decode
        # chunk traced WITH the Pallas paged-decode kernel engaged.
        # interpret mode lowers the kernel to plain HLO on any backend,
        # so the canonical audit pins the kernel-bearing program's
        # donation/budget/dtype discipline even on the CPU manifest.
        # TL_PAGED_KERNEL is read at TRACE time, so the env toggle must
        # wrap the lazy ``lower()`` thunk, not this enumeration
        pkern = PagedContinuousBatchingEngine(
            eng, block_size=8, prefill_chunk=16, kv_quant="int8", **kw
        )
        for it in pkern.audit_programs():
            if it["name"] != "decode":
                continue

            def _lower_with_kernel(_base=it["lower"]):
                prev = os.environ.get("TL_PAGED_KERNEL")
                os.environ["TL_PAGED_KERNEL"] = "interpret"
                try:
                    return _base()
                finally:
                    if prev is None:
                        os.environ.pop("TL_PAGED_KERNEL", None)
                    else:
                        os.environ["TL_PAGED_KERNEL"] = prev

            it["lower"] = _lower_with_kernel
            it["name"] = f"paged_kernel.{it['name']}"
            it["group"] = "paged_kernel"
            out.append(it)
        return out

    # serving engines carry their own group prefixes (two groups from
    # one builder) — on failure, record a skip under EACH prefix so the
    # manifest's continuous.*/paged.* pins stay shielded, not "missing"
    def serving_group() -> None:
        try:
            programs.extend(serving_engines())
        except Exception as e:  # noqa: BLE001
            why = f"{type(e).__name__}: {e}"
            skipped.append(("continuous", why))
            skipped.append(("paged", why))

    serving_group()

    def pipeline_group() -> list[dict]:
        from tensorlink_tpu.parallel.pipeserve import PipelineStageEngine

        cfg = LlamaConfig.tiny()
        m = Llama(cfg)
        p = m.init(key)
        eng = InferenceEngine(
            make_mesh(MeshConfig()), m, p, max_len=64,
            cache_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        )
        # a 2-stage cut through the tiny stack: the per-stage budget is
        # ONE decode + ONE prefill program REGARDLESS of request mix —
        # total program count scales with stage count only (TLH105)
        spans = [(0, 1), (1, cfg.num_layers)]
        out: list[dict] = []
        for stage, (lo, hi) in enumerate(spans):
            seng = PipelineStageEngine(
                eng, lo=lo, hi=hi, sid="audit", stage=stage,
                n_stages=len(spans), slots=2, block_size=8,
                prefill_chunk=16,
            )
            for it in seng.audit_programs():
                it["name"] = f"stage{stage}_{it['name']}"
                out.append(it)
        return out

    _try("pipeline", pipeline_group)

    def trainer_group() -> list[dict]:
        from tensorlink_tpu.models.gpt2 import GPT2, GPT2Config
        from tensorlink_tpu.train.trainer import (
            Trainer,
            softmax_cross_entropy,
        )

        gm = GPT2(GPT2Config(
            vocab_size=64, dim=16, num_layers=2, num_heads=2, max_len=32,
            dropout=0.0,
        ))

        def loss_fn(module, params, batch, rng):
            return softmax_cross_entropy(
                module.apply(params, batch["input_ids"]), batch["labels"]
            )

        tr = Trainer(gm, loss_fn, TrainConfig(
            batch_size=2, micro_batches=1, learning_rate=1e-2,
            dtype="bfloat16", optimizer="adamw",
        ))
        state = tr.init_state(key)
        batch = {
            "input_ids": jnp.zeros((2, 8), jnp.int32),
            "labels": jnp.zeros((2, 8), jnp.int32),
        }
        return tr.audit_programs(state, batch, key)

    _try("trainer", trainer_group)

    def sharded_group() -> list[dict]:
        from tensorlink_tpu.models.gpt2 import GPT2, GPT2Config
        from tensorlink_tpu.parallel.engine import ShardedTrainer
        from tensorlink_tpu.train.trainer import softmax_cross_entropy

        if len(jax.devices()) < 2:
            raise TooFewDevices("needs >= 2 devices for a pipe mesh")
        gm = GPT2(GPT2Config(
            vocab_size=64, dim=16, num_layers=2, num_heads=2, max_len=32,
            dropout=0.0,
        ))
        gp = gm.init(key)
        parts = gm.as_pipeline_parts(gp)
        tr = ShardedTrainer(
            make_mesh(MeshConfig(pipe=2)),
            TrainConfig(batch_size=2, micro_batches=2, learning_rate=1e-2,
                        optimizer="sgd", dtype="bfloat16"),
            parts,
            lambda lg, b: softmax_cross_entropy(lg, b["labels"]),
        )
        batch = {
            "input_ids": jnp.zeros((2, 8), jnp.int32),
            "labels": jnp.zeros((2, 8), jnp.int32),
        }
        return tr.audit_programs(tr.init_state(), batch)

    _try("sharded", sharded_group)

    def worker_group() -> list[dict]:
        from tensorlink_tpu.models.mlp import MLP, MLPConfig
        from tensorlink_tpu.roles.worker import StageRunner

        sm = MLP(MLPConfig(in_dim=16, hidden_dim=32, out_dim=16,
                           num_layers=2))
        sp = sm.init(key)
        runner = StageRunner(
            job_id="tlhlo", stage_index=0, module=sm, params=sp,
            opt=None, opt_state=None,
        )
        return runner.audit_programs(
            jax.ShapeDtypeStruct((4, 16), jnp.float32)
        )

    _try("worker", worker_group)

    def infer_group() -> list[dict]:
        ndev = len(jax.devices())
        if ndev < 4:
            raise TooFewDevices(
                f"kv_seq_shard needs a seq=4 mesh, only {ndev} device(s)"
            )
        cfg = LlamaConfig(
            vocab_size=64, dim=32, num_layers=2, num_heads=4,
            num_kv_heads=4, hidden_dim=64, max_len=512,
        )
        m = Llama(cfg)
        p = m.init(key)
        eng = InferenceEngine(
            make_mesh(MeshConfig(seq=4)), m, p, max_len=512,
            cache_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
            kv_seq_shard=True,
        )
        return [eng.audit_decode_program(
            1, 16, GenerationConfig(max_new_tokens=64),
            name="kv_shard_decode",
        )]

    _try("infer", infer_group)

    def want(name: str) -> bool:
        if only and not any(fnmatch.fnmatch(name, g) for g in only):
            return False
        if skip and any(fnmatch.fnmatch(name, g) for g in skip):
            return False
        return True

    return [p for p in programs if want(p["name"])], skipped


def run_audit(
    only: list[str] | None = None, skip: list[str] | None = None,
) -> tuple[list[ProgramAudit], list[tuple[str, str]]]:
    """Enumerate + lower + compile + parse the canonical programs."""
    progs, skipped = canonical_programs(only, skip)
    audits = []
    for p in progs:
        try:
            lowered = p["lower"]()
        except Exception as e:  # noqa: BLE001 — report, keep auditing
            skipped.append((p["name"], f"{type(e).__name__}: {e}"))
            continue
        audits.append(audit_lowered(
            p["name"], lowered, group=p["group"], dtype=p["dtype"],
            donated=p["donated"],
        ))
    return audits, skipped


# ------------------------------------------------------------------- CLI
def render_findings(
    findings: Iterable[Finding], fmt: str,
    extra: dict[str, Any] | None = None,
) -> str:
    """Findings in the CLI's text/json/github shapes (the github form
    is the ::error workflow-command grammar — single-line messages)."""
    findings = list(findings)
    if fmt == "json":
        return json.dumps(
            {"findings": [f.to_json() for f in findings], **(extra or {})},
            indent=2,
        )
    lines = []
    if fmt == "github":
        for f in findings:
            lines.append(github_annotation(f, "tlhlo"))
    else:
        for f in findings:
            lines.append(f"{f.path}: {f.rule} {f.message}")
    return "\n".join(lines)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tlhlo",
        description=(
            "Audit the framework's compiled programs: donation honored, "
            "collective/memory budgets, dtype discipline, host "
            "round-trips, program-count budgets — pinned by "
            f"{MANIFEST_NAME}."
        ),
    )
    p.add_argument(
        "--manifest", metavar="FILE", default=None,
        help=(
            f"manifest file (default: nearest {MANIFEST_NAME} above the "
            "CWD; 'none' audits without pins — only the live rules run)"
        ),
    )
    p.add_argument(
        "--write-manifest", action="store_true",
        help="pin the current audit as the manifest and exit 0 "
             "(suppress reasons and skipped programs' pins preserved)",
    )
    p.add_argument(
        "--format", choices=("text", "json", "github"), default="text",
    )
    p.add_argument(
        "--only", action="append", metavar="GLOB",
        help="audit only programs matching this glob (repeatable), "
             "e.g. --only 'paged.*'",
    )
    p.add_argument(
        "--skip", action="append", metavar="GLOB",
        help="skip programs matching this glob (repeatable)",
    )
    p.add_argument(
        "--tolerance", type=float, default=0.10,
        help="relative slack for TLH106 memory pins (default 0.10)",
    )
    p.add_argument(
        "--list-programs", action="store_true",
        help="enumerate the canonical programs (no compile) and exit",
    )
    p.add_argument(
        "--list-rules", action="store_true",
        help="list the TLH rule ids with one-line summaries and exit",
    )
    p.add_argument(
        "--explain", metavar="RULE",
        help="print the full explanation for a rule id and exit",
    )
    return p


def main(argv: list[str] | None = None) -> int:
    # env defaults: the canonical audit is a CPU-lowering tool, and the
    # kv-shard program needs a multi-device virtual mesh. jax's backend
    # builds LAZILY on first device query, so setting these is effective
    # even though importing this package already imported jax — only a
    # process that initialized the backend beforehand (an in-process
    # test harness, a TPU operator) keeps its own runtime, and the
    # enumeration then adapts by skipping the groups it cannot mesh.
    from tensorlink_tpu.runtime.mesh import virtual_cpu_xla_flags

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = virtual_cpu_xla_flags(
        8, os.environ.get("XLA_FLAGS", "")
    )

    args = build_parser().parse_args(argv)
    if args.list_rules:
        for rule in sorted(HLO_RULES):
            print(f"{rule}  {HLO_RULES[rule].strip().splitlines()[0]}")
        return 0
    if args.explain:
        doc = HLO_RULES.get(args.explain)
        if not doc:
            print(f"unknown rule {args.explain}", file=sys.stderr)
            return 2
        print(f"{args.explain}: {doc}")
        return 0
    if args.list_programs:
        progs, skipped = canonical_programs(args.only, args.skip)
        for p in progs:
            don = f" donate={p['donated']}" if p["donated"] else ""
            print(f"{p['name']}  [{p['dtype']}]{don}")
        for name, why in skipped:
            print(f"# skipped {name}: {why}")
        return 0

    manifest_path = args.manifest
    if manifest_path is None:
        manifest_path = find_default_manifest(".")
    elif manifest_path == "none":
        manifest_path = None

    audits, skipped = run_audit(args.only, args.skip)
    if not audits:
        print("tlhlo: no programs audited", file=sys.stderr)
        for name, why in skipped:
            print(f"tlhlo: skipped {name}: {why}", file=sys.stderr)
        return 2

    if args.write_manifest:
        path = manifest_path or MANIFEST_NAME
        write_manifest(path, audits, skipped)
        print(f"tlhlo: pinned {len(audits)} program(s) to {path}")
        for name, why in skipped:
            print(f"tlhlo: skipped {name}: {why}")
        return 0

    manifest = None
    if manifest_path is not None:
        try:
            manifest = load_manifest(manifest_path)
        except (OSError, ValueError) as e:
            print(f"tlhlo: bad manifest: {e}", file=sys.stderr)
            return 2

    def selected(name: str) -> bool:
        if args.only and not any(
            fnmatch.fnmatch(name, g) for g in args.only
        ):
            return False
        if args.skip and any(fnmatch.fnmatch(name, g) for g in args.skip):
            return False
        # a program this run could not enumerate (env gap) is "skipped",
        # not "missing" — it keeps its manifest pin
        if any(name == n or name.startswith(n + ".") for n, _ in skipped):
            return False
        return True

    findings = audit_findings(
        audits, manifest, tolerance=args.tolerance, selected=selected,
    )
    suppressed: dict[str, str] = {}
    if manifest is not None:
        for e in manifest.get("suppress", []):
            if isinstance(e, dict) and "fingerprint" in e:
                suppressed[e["fingerprint"]] = e.get("reason", "")
            elif isinstance(e, str):
                suppressed[e] = ""
    fresh = [f for f in findings if f.fingerprint not in suppressed]
    known = len(findings) - len(fresh)

    extra = {
        "programs": {a.name: a.record() for a in audits},
        "skipped": [list(s) for s in skipped],
        "suppressed": known,
    }
    out = render_findings(fresh, args.format, extra)
    if out:
        print(out)
    if args.format != "json":
        for name, why in skipped:
            print(f"tlhlo: skipped {name}: {why}")
        tail = f" ({known} suppressed)" if known else ""
        print(
            f"tlhlo: {len(fresh)} finding(s) over {len(audits)} "
            f"program(s){tail}"
        )
    return 1 if fresh else 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
