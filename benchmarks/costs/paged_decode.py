"""Operations and bytes of one paged decode-attention call over all layers'
lanes: one query token a lane against the K/V it has cached.

q: [slots, NH, HD]; each live lane holds `context` tokens of K and V
(`mean_context` on average over live lanes).  Operations: QK^T and PV,
2 * 2 * live * NH * HD * context.  Bytes, the least the algorithm moves:
the cached K and V of the live lanes once (2 * live * context * NH * HD *
itemsize) plus q and the output.  One call is one layer; the reader
multiplies by the calls it finds.
"""


def cost(live, mean_context, NH, HD, itemsize=2):
    kv = 2 * live * mean_context * NH * HD * itemsize
    return {"ops": 2 * 2 * live * NH * HD * mean_context,
            "bytes": kv + 2 * live * NH * HD * itemsize}


def for_window(run, calls, sz):
    """All the window's calls at once: every token a client received in
    the window past a request's first came from one decode step of its
    lane, at a context of the prompt plus the tokens before it, in each
    layer.  (`calls` is layers x steps; what a step costs depends on who
    was live, so the sum over the window is what can be known.)"""
    t0, t1 = run.window
    context = 0
    for r in run.client["records"]:
        p = len(run.requests[r["id"]]["prompt"])
        context += sum(p + i for i, t in enumerate(r["t"])
                       if i > 0 and t0 <= t < t1)
    c = cost(1, context, sz["NH"], sz["HD"], 2)
    return {"ops": c["ops"] * sz["L"], "bytes": c["bytes"] * sz["L"]}
