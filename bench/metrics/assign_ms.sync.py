"""Mean host milliseconds of ``assigner.assign`` per round, from the
framework's own ``assign_latency_s`` record."""


def read(run):
    units = run["units"]
    return 1e3 * sum(u["assign_s"] for u in units) / len(units)
