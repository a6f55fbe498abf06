"""Median time from submit to the host's holding the first token as an
int, of the requests whose first token fell in the traced window:
``ttft_ms`` of the ``tl.serve.first_token`` events, on the engine's own
clock (reader 11 of ISSUE 26)."""

import statistics

from benchmark import spans


def read(run):
    ttft = spans.event_values(run, "tl.serve.first_token", "ttft_ms")
    return statistics.median(ttft) if ttft else None
