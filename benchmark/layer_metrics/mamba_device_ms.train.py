"""Device self time a step of a Phi-4-mini-flash model's Mamba halves:
the instructions whose innermost ``tl.`` scope is ``tl.mamba`` (norm,
projections, short convolution, step, gate, residual) or
``tl.mamba.scan`` (the selective scan), forward, recomputed forward and
backward; per launch of ``jit_tl_train_step``, median."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.mamba")
