"""Useful training operations of the window's rounds (sum of the
cohort's valid D_n, times L local steps, times Q edge iterations) over
the window and the chip's bf16 peak."""
from bench.metrics._common import mfu_percent


def read(run):
    s = run["config"]["system"]
    return mfu_percent(run, sum(u["samples"] for u in run["units"])
                       * s["L"] * s["Q"])
