"""Operations and bytes of one-token decode attention over grouped KV heads
with window layers, over all layers' lanes: the work the MODEL needs,
whatever kernel implements it, so that a later kernel is judged on the
same work.

A live lane at context c reads, in a layer that sees everything, c tokens
of K and V of NKV heads of HD; in a layer with a window, min(c, WINDOW).
Bytes count the KV heads, not the query heads (a KV head's keys are read
once for its group of query heads): 2 * tokens * NKV * HD * itemsize, plus
q and the output (NH * HD each).  Operations: QK^T and PV for every query
head, 2 * 2 * NH * HD * tokens.  One call is one layer; `for_window` sums
the window's calls of all layers at once.
"""


def cost(tokens, lanes, NH, NKV, HD, itemsize=2):
    """`tokens`: cached tokens read, summed over the lanes' steps in one
    layer; `lanes`: the lane-steps themselves."""
    return {"ops": 2 * 2 * NH * HD * tokens,
            "bytes": (2 * tokens * NKV * HD
                      + 2 * lanes * NH * HD) * itemsize}


def for_window(run, calls, sz):
    """Every token a client received in the window past a request's first
    came from one decode step of its lane, at a context of the prompt plus
    the tokens before it; a full layer reads all of it, a window layer
    the last WINDOW of it."""
    t0, t1 = run.window
    full = windowed = lanes = 0
    for r in run.client["records"]:
        p = len(run.requests[r["id"]]["prompt"])
        for i, t in enumerate(r["t"]):
            if i > 0 and t0 <= t < t1:
                full += p + i
                windowed += min(p + i, sz["WINDOW"])
                lanes += 1
    a = cost(full, lanes, sz["NH"], sz["NKV"], sz["HD"])
    b = cost(windowed, lanes, sz["NH"], sz["NKV"], sz["HD"])
    return {k: a[k] * sz["L_FULL"] + b[k] * sz["L_WINDOW"] for k in a}
