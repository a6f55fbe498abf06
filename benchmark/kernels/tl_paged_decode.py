"""Operations and bytes one call of the paged-decode attention kernel
(``ops/pallas/paged_decode.py``, one layer) needs, from shapes and live
context lengths, never from which code ran.

A call serves rows; row r has ``T_r`` queries, each attending the
row's live context. FLOPs: QK^T and PV, 2 * 2 * H * D per (query,
key) pair. Bytes: the K and V of every live token read ONCE per KV
head (grouped query heads share them), the queries read and the output
written once.
"""


def work(cfg: dict, queries: float, context: float, pairs: float,
         itemsize: int = 2) -> tuple[float, float]:
    """``queries`` = sum of T_r, ``context`` = sum of live tokens over
    the rows, ``pairs`` = sum over queries of the keys each attends."""
    H, G, D = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    flops = 4.0 * H * D * pairs
    nbytes = itemsize * (2.0 * G * D * context + 2.0 * H * D * queries)
    return flops, nbytes
