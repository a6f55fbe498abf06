"""Host time of one scheduler turn that is the scheduler's own: the
duration of ``tl.serve.step`` less the ``tl.serve.drain`` inside it
(the wait for the device), median over the traced window's turns."""

from benchmark import spans


def read(run):
    return spans.sched_host_ms(run)
