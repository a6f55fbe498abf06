"""Device self time a launch of ``jit_tl_decode`` of the instructions
whose innermost ``tl.`` scope is ``tl.serve.sample`` (sampling from the
last logits), median over the traced window's launches. ``None`` where
the compiler left no instruction of its own under the scope."""

from benchmark import spans


def read(run):
    return spans.group_ms(run, "sample", "tl_decode") or None
