"""Host time a scheduler turn under ``tl.serve.prefill_dispatch`` (the
host side of the turn's prefill (chunk) launch), median over the traced
window's turns that hold the phase."""

from benchmark import spans


def read(run):
    return spans.phase_ms(run, "prefill_dispatch")
