"""Share of the traced window in which no instruction ran on the chip."""


def read(run):
    return 100.0 * (1.0 - run["trace"].busy_ns / 1e9 / run["window_s"])
