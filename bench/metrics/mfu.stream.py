"""Useful training operations of the updates aggregated in the window,
each counted as L local steps over the mean D_n of its round's cohort,
over the window and the chip's bf16 peak."""
from bench.metrics._common import mfu_percent


def read(run):
    L = run["config"]["system"]["L"]
    return mfu_percent(run, sum(u["updates"] * u["mean_d"] * L
                                for u in run["units"]))
