"""Operations and bytes one call of each flash-attention kernel
(``ops/pallas/flash_attention.py``) needs, from shapes. Causal: half of
the (query, key) pairs exist.

forward   S = QK^T, O = PV                       2 matmuls
bwd_dq    S, dP = dO V^T, dQ = dS K              3 matmuls
bwd_dkv   S, dP, dV = P^T dO, dK = dS^T Q        4 matmuls
(the two backward kernels each need S and dP for their own output; what
one of them computes is not handed to the other, so both count them).
Bytes: each operand read and each result written once, ``itemsize``
bytes an element; the per-row statistics are left out.
"""

MATMULS = {"tl_flash_fwd": 2, "tl_flash_bwd_dq": 3, "tl_flash_bwd_dkv": 4}
ARRAYS = {"tl_flash_fwd": 4, "tl_flash_bwd_dq": 5, "tl_flash_bwd_dkv": 6}


def work(kernel: str, batch: int, heads: int, seq: int, head_dim: int,
         causal: bool = True, itemsize: int = 2) -> tuple[float, float]:
    pairs = batch * heads * seq * seq * (0.5 if causal else 1.0)
    flops = MATMULS[kernel] * 2.0 * head_dim * pairs
    nbytes = ARRAYS[kernel] * batch * heads * seq * head_dim * itemsize
    return flops, nbytes
