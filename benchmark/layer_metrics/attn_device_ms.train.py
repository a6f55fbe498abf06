"""Device self time a step of the instructions whose innermost ``tl.``
scope is ``tl.attn`` (a block's attention half: norm, projections, the
flash kernels, residual), forward and backward; per launch of
``jit_tl_train_step``, median over the traced window."""

from benchmark import spans


def read(run):
    return spans.group_ms(run, "attn")
