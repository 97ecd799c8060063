"""Device milliseconds of the async dispatch program
``_train_dispatched`` per client update aggregated in the traced
window, from the trace."""
from bench.metrics._common import program_ms_per


def read(run):
    units = run["traced_units"] or ()
    return program_ms_per(run, "_train_dispatched",
                          sum(u["updates"] for u in units))
