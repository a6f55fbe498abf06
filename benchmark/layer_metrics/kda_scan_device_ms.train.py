"""As ``kda_device_ms.train``, for ``tl.kda.scan`` alone: the chunked
gated delta-rule recurrence (``ops/kda.py``), what a kernel for it would
replace."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.kda.scan")
