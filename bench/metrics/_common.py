"""Arithmetic shared by the metric readers. Each reader is ``read(run)``
over the run's dict (see ``bench/run.py``) and returns a number, or None
when the run holds nothing for it to read."""
from __future__ import annotations

from bench import flops


def idle_percent(run):
    """Share of the traced window in which no operation ran on the
    device, averaged over the chips used."""
    t = run["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def program_ms_per(run, program, per):
    """Device milliseconds of ``program`` per chip, per ``per`` units."""
    t = run["trace"]
    if t is None or program not in t["program_s"] or not per:
        return None
    return 1e3 * t["program_s"][program] / t["chips"] / per


def mfu_percent(run, sample_steps):
    """Useful training operations of ``sample_steps`` over the window
    that runs without the profiler, as a share of the chips' bf16 peak;
    read on the chip only."""
    if run["peaks"] is None:
        return None
    ops = flops.train_flops(run["config"]["model"], sample_steps)
    return 100.0 * ops / run["window_s"] / (
        run["peaks"]["flops_bf16"] * run["chips"])
