"""Host time a scheduler turn under ``tl.serve.drain`` (the host's wait
for the oldest chunks in flight: the device's time, not the host's),
median over the traced window's turns that hold the phase."""

from benchmark import spans


def read(run):
    return spans.phase_ms(run, "drain")
