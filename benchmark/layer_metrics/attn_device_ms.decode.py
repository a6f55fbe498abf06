"""Device self time a launch of ``jit_tl_decode`` of the instructions
whose innermost ``tl.`` scope is ``tl.attn`` (a block's attention half:
norm, projections, the paged kernel or its glue, residual), median over
the traced window's launches. ``None`` where the compiler left no
instruction of its own under the scope."""

from benchmark import spans


def read(run):
    return spans.group_ms(run, "attn", "tl_decode") or None
