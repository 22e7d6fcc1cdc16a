"""Operations and bytes of causal flash attention, from shapes.

q, k, v: [B, S, NH, HD] in `itemsize` bytes.  Forward: two matrix
products (QK^T and PV) over the causal half of the S x S square,
2 * 2 * B * NH * S * S * HD / 2 operations.  Backward (dQ, dK, dV and the
recomputed QK^T and dP = dO V^T): five products over the same half,
2.5 times the forward.  Bytes, the least the algorithm moves: forward
reads q, k, v and writes o (4 tensors) and the log-sum-exp row (float32);
backward reads q, k, v, o, do and writes dq, dk, dv (8 tensors).
"""


def cost(B, S, NH, HD, itemsize=2, backward=False):
    tensor = B * S * NH * HD * itemsize
    fwd_ops = 2 * 2 * B * NH * S * S * HD / 2
    if backward:
        return {"ops": 2.5 * fwd_ops, "bytes": 8 * tensor + B * NH * S * 4}
    return {"ops": fwd_ops, "bytes": 4 * tensor + B * NH * S * 4}


def for_window(run, calls, sz, backward=False):
    c = cost(sz["B"], sz["S"], sz["NH"], sz["HD"], 2, backward)
    return {"ops": c["ops"] * calls, "bytes": c["bytes"] * calls}
