"""Share of the step program's device self time that lies under any
``tl.`` scope: what the four ``*_device_ms.train`` metrics leave
unexplained is 100 less this. Per launch of ``jit_tl_train_step``,
medians."""

from benchmark import spans


def read(run):
    split = spans.step_split(run)
    if split is None or not split["total"]:
        return None
    return 100.0 * split["scoped"] / split["total"]
