"""Host time a scheduler turn under ``tl.serve.grow_blocks`` (block-
table growth ahead of the decode frontier (paged engine)), median over
the traced window's turns that hold the phase."""

from benchmark import spans


def read(run):
    return spans.phase_ms(run, "grow_blocks")
