"""Operations and bytes one call of each flash-attention kernel needs
when q and k are wider than v (MLA without rotary: q, k 128 + 64, v
128), from shapes; the needed work, whatever a kernel pads or splits.
Causal: half of the (query, key) pairs exist. A matmul against q or k
(S = QK^T, dQ = dS K, dK = dS^T Q) contracts or produces ``d_qk``, one
against v or dO (O = PV, dP = dO V^T, dV = P^T dO) ``d_v``:

forward   S, O                     d_qk + d_v
bwd_dq    S, dP, dQ                2 d_qk + d_v
bwd_dkv   S, dP, dV, dK            2 d_qk + 2 d_v
(as ``tl_flash.py``: each backward kernel needs S and dP for itself).
Bytes: each operand read and each result written once; the per-row
statistics are left out.
"""

# (matmuls at d_qk, matmuls at d_v), (arrays d_qk wide, arrays d_v wide)
MATMULS = {
    "tl_flash_fwd": (1, 1), "tl_flash_bwd_dq": (2, 1),
    "tl_flash_bwd_dkv": (2, 2),
}
ARRAYS = {  # q k | v o;  q k dq | v do;  q k dk | v do dv
    "tl_flash_fwd": (2, 2), "tl_flash_bwd_dq": (3, 2),
    "tl_flash_bwd_dkv": (3, 3),
}


def work(kernel: str, batch: int, heads: int, seq: int, d_qk: int, d_v: int,
         causal: bool = True, itemsize: int = 2) -> tuple[float, float]:
    pairs = batch * heads * seq * seq * (0.5 if causal else 1.0)
    at_qk, at_v = MATMULS[kernel]
    flops = 2.0 * (at_qk * d_qk + at_v * d_v) * pairs
    wide, narrow = ARRAYS[kernel]
    nbytes = batch * heads * seq * (wide * d_qk + narrow * d_v) * itemsize
    return flops, nbytes
