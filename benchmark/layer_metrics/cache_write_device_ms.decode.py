"""Device self time a launch of ``jit_tl_decode`` of the instructions
whose innermost ``tl.`` scope is ``tl.serve.cache_write`` (KV and index
bookkeeping written back into the engine's state), median over the
traced window's launches. ``None`` where the compiler left no
instruction of its own under the scope."""

from benchmark import spans


def read(run):
    return spans.group_ms(run, "cache_write", "tl_decode") or None
