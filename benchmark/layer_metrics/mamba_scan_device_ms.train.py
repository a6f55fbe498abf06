"""As ``mamba_device_ms.train``, for ``tl.mamba.scan`` alone: the
selective scan (``ops/selective_scan.py``), forward and backward, what a
kernel for it would replace."""

from benchmark import scope_ms


def read(run):
    return scope_ms.read(run, "tl.mamba.scan")
