"""As ``kda_device_ms.train``, for ``tl.moe`` with ``tl.moe.route`` and
``tl.moe.experts``: a Kimi-Linear model's expert halves (norm, router,
sort, the held experts' grouped matmuls, shared expert, residual). The
grouped matmuls carry no op path and are counted by name, as
``moe_experts_device_ms.train`` counts them."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.moe", unscoped=scope_ms.GROUPED_MATMULS)
