"""As ``kda_device_ms.train``, for ``tl.moe.experts`` alone: the gather
of routed rows, the three grouped matmuls of the held experts, the
scatter back. The grouped matmuls reach the trace as ``%ragged-dot*``
custom calls without an op path (``scope_ms``): they are counted here
by name, since nothing else in a train step is a grouped matmul, and
``scoped_device_pct.train`` reads them as unscoped all the same."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(
        run, "tl.moe.experts", unscoped=scope_ms.GROUPED_MATMULS)
