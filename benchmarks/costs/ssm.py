"""Operations and bytes of the selective state-space scan of a model whose
layers hold a recurrent state (Mamba-2: SSM_H heads of [SSM_P, SSM_N]
float32 a layer, L_STATE such layers, chunks of SSM_CHUNK tokens), counted
from the MODEL's work, whatever kernel walks it, so that a later kernel is
judged on the same work.

The one-token update (`paddle_ssm_decode_update`) reads and writes the
state of every LIVE lane once a layer a step: 2 x H x P x N x 4 bytes a
lane-step a layer, and five operations an element (the decay's product,
the outer product and its sum, the product with C and its sum).  A kernel
that touches all the slots moves more and reads low against this.

The chunked scan (`paddle_ssd_chunk_scan`), a pass of t tokens in one
layer: inside each chunk C B^T and the masked product with dt x, of which
the causal half is work (q (N + H P) operations a token at q tokens in the
chunk before it, 2 a multiply-add); the state's part of y and the chunk's
part of the state, 2 N H P each a token.  Bytes: the state in and out once
a pass, and a token's x, y, B and C.  Tokens are the TRUE (unpadded) ones
the program's counters give; a pass's length is taken as the mean of the
window's, which counts the squares low (never high).
"""


def update_cost(lane_steps, layers, H, P, N):
    """`lane_steps`: live lanes summed over the window's decode steps."""
    state = H * P * N
    return {"ops": 5 * lane_steps * layers * state,
            "bytes": 2 * 4 * lane_steps * layers * state}


def scan_cost(tokens, passes, layers, H, P, N, chunk, itemsize=2):
    """`tokens`: true tokens scanned over the window; `passes`: the prompt
    passes (admissions and chunks) they came in."""
    if not passes:
        return {"ops": 0, "bytes": 0}
    mean = tokens / passes
    whole, rest = divmod(mean, chunk)
    inside = (whole * chunk * chunk + rest * rest) * (N + H * P)
    ops = passes * (inside + 4 * mean * N * H * P)
    nbytes = passes * 2 * 4 * H * P * N \
        + tokens * (H * P * (itemsize + 4) + 2 * N * itemsize)
    return {"ops": layers * ops, "bytes": layers * nbytes}


def for_window(run, calls, sz, kind):
    """All the window's calls at once, from the program's own counters
    (`calls`, layers x steps or passes, is not needed: the counters already
    are sums over them)."""
    dims = (sz["L_STATE"], sz["SSM_H"], sz["SSM_P"], sz["SSM_N"])
    if kind == "update":
        return update_cost(run.counters.get("state_lane_steps", 0), *dims)
    return scan_cost(run.counters.get("state_scan_tokens", 0),
                     run.counters.get("state_scans", 0), *dims,
                     sz["SSM_CHUNK"])
