"""Device milliseconds of the fused round program ``round_step`` per
round of the traced window, from the trace."""
from bench.metrics._common import program_ms_per


def read(run):
    return program_ms_per(run, "round_step", len(run["traced_units"] or ()))
