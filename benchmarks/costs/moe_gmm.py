"""Operations and bytes of the grouped expert FFN (`paddle_moe_gmm`) from
what was routed, not from how the kernel tiles it.

One assignment (a token routed to one of its experts) is a row through
three H x F matrices: gate and up (H -> F each) and down (F -> H):
2 * 3 * H * F operations.  Bytes, the least the algorithm moves: the three
matrices of every expert that took at least one row, read once a call,
plus each row in and out (H each, at `itemsize`).  Rows that only pad a
group to the kernel's tile are nobody's work and count for nothing.
"""


def cost(assignments, experts_touched, H, F, itemsize=2):
    return {"ops": 2 * 3 * assignments * H * F,
            "bytes": (experts_touched * 3 * H * F
                      + 2 * assignments * H) * itemsize}


def for_window(run, calls, sz):
    """All the window's calls at once, from the program's own counters:
    the assignments of live lanes' rows the device counted over the
    window's block steps, and the experts they touched (summed over steps
    and layers).  `calls` (layers x steps) is not needed: the counters
    already are sums over them."""
    return cost(run.counters.get("moe_assignments", 0),
                run.counters.get("moe_experts_touched", 0),
                sz["H"], sz["F"], 2)
