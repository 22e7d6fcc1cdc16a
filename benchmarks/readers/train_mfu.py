"""Model FLOP/s utilization of a `fit` cell: tokens/s of the window times
the operations a token needs (a function under `costs/`) over the bf16
peak of the chips used, in percent."""
from benchmarks import common


def read(run, cost):
    if "train_tokens" not in run.counts or run.peaks is None:
        return None
    t0, t1 = run.window
    ops = common.plugin("costs", cost).ops_per_token(
        run.config["model"], run.traffic["seq"])
    rate = run.counts["train_tokens"] / (t1 - t0)
    return 100.0 * rate * ops / (run.cell["chips"]
                                 * run.peaks["bf16_flops_per_s"])
