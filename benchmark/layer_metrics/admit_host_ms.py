"""Host time a scheduler turn under ``tl.serve.admit`` (deadline expiry
and admission of waiting requests into free slots), median over the
traced window's turns that hold the phase."""

from benchmark import spans


def read(run):
    return spans.phase_ms(run, "admit")
