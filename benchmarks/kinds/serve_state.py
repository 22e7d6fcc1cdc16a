"""Traffic kind `serve_state`: `serve_own` (imported and not copied: its
server, load generator, window and checks) for a model that holds a
recurrent state beside its pages, with one more number in `correct`: the
state a finished request LEFT IN ITS SLOT against the reference's scan of
the same tokens.

Why: `serve`'s check reads served tokens, and the greedy tokens of random
weights hardly feel a state that is a little wrong: a prefix hit that scans
from zero instead of its snapshot, or from another prefix's, serves nearly
the tokens of a sound run (`traffic/toolchat.json` `limits_from` has the
readings).  The state itself does feel it.  A release leaves a slot's state
as it is, so after the server has drained each used slot holds what its
last lane ended in: the restored snapshot, the scan of the own part and
every decode step's update, in one array.  The adapter keeps a few slots'
(`held_states`, taken as the program is freed); here each is matched to the
one finished request whose length and last token the slot's registers show,
and compared with `reference.states_after` of that request's prompt and
tokens: the norm of the difference over the reference's norm, the states of
all layers as one vector and the convolutions' tails as another.

    "state_check_requests": 3, "limits": {"held_state": ...}
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from benchmarks.kinds import serve_own


def finished(run):
    """The client's records of requests that came back whole."""
    return [r for r in run.client["records"]
            if r["done"] and not r["error"] and not r["cut"]
            and len(r["tokens"]) == run.requests[r["id"]]["max_new"]]


def restored_ids(run):
    """The ids of the requests whose admission found a snapshot, told from
    the order of arrival: the first request of a shared prefix leaves
    pages, the second a snapshot at their end, every later one restores it
    (as long as no snapshot is evicted: the run's counters are printed
    beside)."""
    n = run.traffic["prompt"]["shared"]["tokens"]
    seen, out = {}, set()
    for r in sorted(run.client["records"], key=lambda r: r["due"]):
        head = hash(tuple(run.requests[r["id"]]["prompt"][:n]))
        if seen.get(head, 0) >= 2:
            out.add(r["id"])
        seen[head] = seen.get(head, 0) + 1
    return out


def rel_err(got, want):
    return float(np.linalg.norm((got - want).ravel())
                 / max(np.linalg.norm(want.ravel()), 1e-30))


def head_err_max(got, want):
    """The same a head ([layers, heads, ...]), the worst head's: a note
    beside the number compared, for whoever sharpens it (a slow head keeps
    what a restore put there, the sum over all heads mostly does not)."""
    flat = (len(want) * want.shape[1], -1)
    off = np.linalg.norm((got - want).reshape(flat), axis=1)
    return float((off / np.maximum(
        np.linalg.norm(want.reshape(flat), axis=1), 1e-30)).max())


def drive(run):
    import jax
    import jax.numpy as jnp

    serve_own.drive(run)
    spec, cfg = run.traffic, run.config["model"]
    held = common.plugin("adapters", run.config["adapter"]).take_held()
    ref = common.plugin("reference", run.config["reference"])
    # a lane's last step leaves pos = prompt + tokens - 1 and tok = its last
    # token: the one finished request that fits is the slot's last lane
    by_end = {}
    for r in finished(run):
        end = len(run.requests[r["id"]]["prompt"]) + len(r["tokens"]) - 1
        by_end.setdefault((end, r["tokens"][-1]), []).append(r)
    found = [(h, by_end[h["pos"], h["tok"]][0]) for h in held
             if len(by_end.get((h["pos"], h["tok"]), ())) == 1]
    # the shortest first: what a restore put there fades with every token
    # since (at the cell's size a hit scanned from zero read 0.084 after
    # 150 tokens and 0.042 after 315: limits_from)
    found = sorted(found, key=lambda f: f[0]["pos"])
    found = found[:spec["state_check_requests"]]
    run.checks.add("slots_matched_to_a_finished_request", len(found), 1,
                   ok=len(found) >= 1,
                   note=f"of {len(held)} slots looked at")
    if not found:
        return
    t_ref = time.monotonic()
    restored = restored_ids(run)
    w = jax.jit(lambda key: ref.init_weights(
        key, cfg, jnp.dtype(spec["weights_dtype"])))(
            ref.key_from_seed(run.seed))
    fn = jax.jit(lambda w_, ids, n: ref.states_after(w_, ids, n, cfg))
    worst, rows = 0.0, []
    for h, r in found:
        seq = list(run.requests[r["id"]]["prompt"]) + list(r["tokens"][:-1])
        ids = np.zeros((spec["engine"]["max_seq_len"],), np.int32)
        ids[:len(seq)] = seq
        ssm, conv = jax.device_get(fn(w, ids, np.int32(len(seq))))
        row = {"slot": h["slot"], "tokens": len(seq),
               "restored": r["id"] in restored,
               "state": rel_err(h["ssm"], ssm),
               "state_worst_head": head_err_max(h["ssm"], ssm),
               "tail": rel_err(h["conv"], conv)}
        rows.append(row)
        worst = max(worst, row["state"], row["tail"])
    del w
    # serve's check draws its requests among these: where all of them were
    # restored, so were the ones it drew
    t0, until = run.window[0], run.client["collected_until"]
    in_window = [r for r in finished(run) if t0 <= r["t"][-1] < until]
    common.note(state_reference_seconds=time.monotonic() - t_ref,
                held_states=rows,
                window_finished_restored=[
                    sum(r["id"] in restored for r in in_window),
                    len(in_window)],
                window_state_restores=run.counters.get("state_restores"),
                window_state_scans=run.counters.get("state_scans"))
    run.checks.add(
        "held_state_rel_err_max", worst, spec["limits"]["held_state"],
        note=("a finished lane's recurrent state and convolution tail as "
              "its slot holds them against the reference's scan, norm of "
              "the difference over the reference's norm; "
              f"{sum(r['restored'] for r in rows)} of {len(rows)} admitted "
              "through a restored snapshot"))
