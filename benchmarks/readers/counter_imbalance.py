"""How unevenly a family of counters filled over the window: the fullest
of the counters `<prefix>.<group>.<member>` over the mean of its group,
the worst group's ratio (for routed experts: layer by expert, 1.0 where
every expert of every layer took the same share).  None where the program
has no such counters or nothing was counted."""


def read(run, prefix):
    groups = {}
    for k, v in run.counters.items():
        if k.startswith(prefix + "."):
            group = k[len(prefix) + 1:].split(".")[0]
            groups.setdefault(group, []).append(v)
    worst = None
    for xs in groups.values():
        if sum(xs) > 0:
            r = max(xs) * len(xs) / sum(xs)
            worst = r if worst is None else max(worst, r)
    return worst
