"""Share of the device's idle time in the traced window (gaps of 10 us
and over, as ``trace.breakdown`` finds them) that lies under the span
in which the program's hot loop dispatches (``tl.train.step``,
``tl.serve.step`` less its drain): the idle the program's host code
answers for. The rest is the harness's. The two clocks of a capture are
laid over each other by the offset the capture itself brackets
(``spans.Scoped.offset``); ``None`` where it brackets none."""

from benchmark import spans


def read(run):
    return spans.idle_in_program_pct(run)
