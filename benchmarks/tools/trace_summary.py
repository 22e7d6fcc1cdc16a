#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and the events that take
most time on each line, with an example of their stats.

    python3 benchmarks/tools/trace_summary.py FILE.xplane.pb [TOP]
"""
import sys


def main(path, top=25):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            tot = {}
            ex = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0) + e.duration_ns
                ex.setdefault(e.name, e)
            for name, d in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                e = ex[name]
                try:
                    stats = {k: str(v)[:120] for k, v in e.stats}
                except Exception as err:  # noqa: BLE001 - a look by hand
                    stats = f"<{err}>"
                print(f"    {d / 1e6:10.3f} ms  {name[:100]!r}  "
                      f"start={e.start_ns} stats={stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
