"""Client updates aggregated into edge models per wall second: all
updates of the rounds completed in the window over the window."""


def read(run):
    return sum(u["updates"] for u in run["units"]) / run["window_s"]
