"""Wall seconds per global round: the whole window over the rounds
completed in it."""


def read(run):
    return run["window_s"] / len(run["units"])
