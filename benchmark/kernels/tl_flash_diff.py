"""Operations and bytes one call of each flash-attention kernel needs in
a differential-attention layer (``nn/diff_attention.py``): q and k
``d_qk`` wide, v a pair's two value heads side by side (``d_v`` = 2
``d_qk``), ``heads`` query heads on ``kv_heads`` key heads, causal, over
a band of ``window`` keys or all of them; the needed work, whatever a
kernel pads, splits or visits. The (query, key) pairs that exist: half
the square without a window (as ``tl_flash.py`` counts it), with one
``sum_t min(t + 1, window)``. Matmuls by width as ``tl_flash_mla.py``:

forward   S, O                     d_qk + d_v
bwd_dq    S, dP, dQ                2 d_qk + d_v
bwd_dkv   S, dP, dV, dK            2 d_qk + 2 d_v
Bytes: each operand read and each result written once, arrays of the
query heads (q, o or do, dq) and of the key heads (k, v, dk, dv) each at
their own head count; the per-row statistics are left out.
"""

MATMULS = {
    "tl_flash_fwd": (1, 1), "tl_flash_bwd_dq": (2, 1),
    "tl_flash_bwd_dkv": (2, 2),
}
# (query-head arrays d_qk wide, d_v wide), (key-head arrays d_qk, d_v)
ARRAYS = {
    "tl_flash_fwd": ((1, 1), (1, 1)),      # q o | k v
    "tl_flash_bwd_dq": ((2, 1), (1, 1)),   # q dq do | k v
    "tl_flash_bwd_dkv": ((1, 1), (2, 2)),  # q do | k dk v dv
}


def pairs(seq: int, window: int | None = None) -> float:
    """(query, key) pairs of one head over a causal sequence."""
    if window is None:
        return 0.5 * seq * seq
    w = min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def work(kernel: str, batch: int, heads: int, seq: int, d_qk: int, d_v: int,
         window: int | None = None, kv_heads: int | None = None,
         itemsize: int = 2) -> tuple[float, float]:
    at_qk, at_v = MATMULS[kernel]
    flops = 2.0 * (at_qk * d_qk + at_v * d_v) * batch * heads * pairs(seq, window)
    (q_qk, q_v), (k_qk, k_v) = ARRAYS[kernel]
    kv_heads = heads if kv_heads is None else kv_heads
    nbytes = batch * seq * itemsize * (
        heads * (q_qk * d_qk + q_v * d_v) + kv_heads * (k_qk * d_qk + k_v * d_v)
    )
    return flops, nbytes
