"""As ``attn_device_ms.train``, for the scopes of ``Trainer._step``:
``tl.train.accumulate`` + ``.sentinel`` + ``.clip`` + ``.optimizer``,
and ``tl.train.cast`` (the dtype policy's casts, in no model layer)."""

from benchmark import spans


def read(run):
    return spans.group_ms(run, "update")
