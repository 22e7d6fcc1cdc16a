"""Operations and bytes of softmax cross-entropy over logits [N, V].

Forward: one pass over the logits for the row maximum, one for the sum of
exponentials (about 4 operations an element: subtract, exp, add, compare);
reads N * V logits, writes N losses and N log-sum-exps (float32).
Backward: softmax minus one-hot times the incoming gradient, about 4
operations an element; reads the logits, writes as many gradients.
It is bound by bytes at every shape: 4 operations for 2 to 4 bytes moved.
"""


def cost(N, V, itemsize=2, backward=False):
    if backward:
        return {"ops": 4 * N * V, "bytes": 2 * N * V * itemsize + 3 * N * 4}
    return {"ops": 4 * N * V, "bytes": N * V * itemsize + 3 * N * 4}


def for_window(run, calls, sz, backward=False):
    c = cost(sz["N"], sz["V"], 2, backward)
    return {"ops": c["ops"] * calls, "bytes": c["bytes"] * calls}
