#!/usr/bin/env python3
"""The second control of a cell whose model holds a recurrent state: a run
of the real program with ONE fault planted in the mechanism the cell exists
for.  Its result line has to say `"correct": false`, by the number that
reads the state (`kinds/serve_state.py`); the benchmark's own runs never
run it.

    python3 benchmarks/tools/state_faults.py --workload NAME --seed N \
        --fault scan_from_zero|stale_snapshot|tail_unwritten [--seconds S]

scan_from_zero: a prefix hit shares its pages and scans its own part from
the zero state, as if no snapshot were restored.  stale_snapshot: it
restores another prefix's snapshot (place 0 for 1 and 1 for every other).
tail_unwritten: one decode step in sixteen (where a lane's position divides
by 16) leaves the lane's convolution tail as it was.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.run import run_cell  # noqa: E402

FAULTS = ("scan_from_zero", "stale_snapshot", "tail_unwritten")


def plant(fault: str):
    """Plant `fault` in the program (before its engine is built); returns
    what takes it out again."""
    import jax.numpy as jnp

    from paddle_tpu.serving import generation, kv_cache

    if fault == "tail_unwritten":
        sound = kv_cache.HybridKV.window

        def window(self, plane, xBC):
            past, out = sound(self, plane, xBC)
            if not isinstance(self.states, kv_cache.LaneStates):
                return past, out
            skip = (self.kv.positions % 16 == 0)[None, :, None]
            return past, kv_cache.replace(out, states=kv_cache.replace(
                out.states, conv=jnp.where(skip, self.states.conv,
                                           out.states.conv)))

        kv_cache.HybridKV.window = window
        return lambda: setattr(kv_cache.HybridKV, "window", sound)
    sound = generation.initial_states
    other = {"scan_from_zero": lambda s: jnp.full_like(
                 s, kv_cache.SCAN_FROM_ZERO),
             "stale_snapshot": lambda s: jnp.where(s == 0, 1, 0)}[fault]

    def initial_states(state, slot, start):
        start = jnp.asarray(start, jnp.int32)
        return sound(state, slot, jnp.where(start >= 0, other(start), start))

    generation.initial_states = initial_states
    return lambda: setattr(generation, "initial_states", sound)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    args = ap.parse_args(argv)
    run_cell(args.workload, args.seed, args.seconds, 0,
             prepare=lambda run: plant(args.fault))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
