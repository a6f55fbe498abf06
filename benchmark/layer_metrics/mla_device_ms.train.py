"""As ``kda_device_ms.train``, for ``tl.mla``: a Kimi-Linear model's
latent-attention halves (norm, projections, the flash kernels at 192 /
128, the layout changes around them, residual)."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.mla")
