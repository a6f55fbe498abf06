"""Host time a scheduler turn under ``tl.serve.decode_dispatch`` (the
host side of the turn's decode / spec chunk launch), median over the
traced window's turns that hold the phase."""

from benchmark import spans


def read(run):
    return spans.phase_ms(run, "decode_dispatch")
