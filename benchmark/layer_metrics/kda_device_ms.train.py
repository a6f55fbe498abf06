"""Device self time a step of a Kimi-Linear model's KDA halves: the
instructions whose innermost ``tl.`` scope is ``tl.kda`` (norm,
projections, short convolutions, gates, head norm, residual) or
``tl.kda.scan`` (the chunked recurrence), forward, recomputed forward
and backward; per launch of ``jit_tl_train_step``, median."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.kda")
