"""Device self time a step of a Laguna model's sliding-window attention
halves: the instructions whose innermost ``tl.`` scope is ``tl.swa``
(norm, projections, rotary, the flash kernels over the band, the heads'
gates, ``o``, residual), forward, recomputed forward and backward; per
launch of ``jit_tl_train_step``, median. The full layers' halves are
``tl.attn`` and read under ``attn_device_ms.train``."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.swa")
