"""As ``attn_device_ms.train``, for ``tl.mlp`` (a block's feed-forward
half: norm, matmuls, activation, residual)."""

from benchmark import spans


def read(run):
    return spans.group_ms(run, "mlp")
