"""Median wait from submit to slot of the requests admitted in the
traced window: ``waited_ms`` of the ``tl.serve.admitted`` events, on
the engine's own clock."""

import statistics

from benchmark import spans


def read(run):
    waited = spans.event_values(run, "tl.serve.admitted", "waited_ms")
    return statistics.median(waited) / 1e3 if waited else None
