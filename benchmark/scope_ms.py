"""Device self time a step under named ``tl.`` scopes, for the readers
of scopes that ``spans.GROUPS`` (a closed table) does not hold. As
``spans.group_ms``: per launch of ``jit_<program>`` that lies whole
inside the window, the self time of the instructions whose innermost
``tl.`` scope is one of ``scopes`` or lies inside one (``tl.kda`` takes
``tl.kda.scan`` with it), forward and backward; the median over
launches, in ms. ``None`` where the program's name is missing or no
instruction carries such a scope (a program from before the scopes).

``unscoped`` names instructions to count although they carry no scope:
the TPU compiler turns ``lax.ragged_dot`` into custom calls
(``%ragged-dot-none.2``, ``%ragged-dot-metadata``) and drops the op
path on the way, so the expert layer's grouped matmuls would be read
under no layer at all. An instruction with no ``tl.`` scope whose name
starts with one of these prefixes (after its ``%``) is counted with the
scopes."""

from __future__ import annotations

import bisect
import statistics

from benchmark import spans


def _under(scope: str | None, scopes: tuple[str, ...]) -> bool:
    return bool(scope) and any(
        scope == s or scope.startswith(s + ".") for s in scopes
    )


# lax.ragged_dot as the TPU compiler names it; nothing else in a train
# step is a grouped matmul
GROUPED_MATMULS = ("ragged-dot",)


def _counts(op, scopes, unscoped) -> bool:
    if op.scope:
        return _under(op.scope, scopes)
    return bool(unscoped) and op.name.lstrip("%").startswith(unscoped)


def read(run: dict, *scopes: str, program: str = "tl_train_step",
         unscoped: tuple[str, ...] = ()):
    sc = spans.of(run)
    if sc is None or not any(_under(o.scope, scopes) for o in sc.ops):
        return None
    launches = [
        m for m in sc.modules_named(program)
        if sc.window is None
        or (m.start >= sc.window[0] and m.end <= sc.window[1])
    ]
    if not launches:
        return None
    key = "tl_starts"  # the ops' starts, kept for the line's next reader
    if key not in run:
        run[key] = [o.start for o in sc.ops]
    starts, totals = run[key], []
    for m in launches:
        i, total = bisect.bisect_left(starts, m.start), 0
        while i < len(sc.ops) and sc.ops[i].start < m.end:
            if _counts(sc.ops[i], scopes, unscoped):
                total += sc.ops[i].self_ns
            i += 1
        totals.append(total)
    return statistics.median(totals) / 1e6
