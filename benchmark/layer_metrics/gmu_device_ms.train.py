"""Device self time a step of a Phi-4-mini-flash model's gated memory
units: the instructions whose innermost ``tl.`` scope is ``tl.gmu``
(norm, the gate's projection, the product with another layer's scan
output, the out-projection, residual), forward, recomputed forward and
backward; per launch of ``jit_tl_train_step``, median."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.gmu")
