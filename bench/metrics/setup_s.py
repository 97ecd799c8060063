"""Seconds from the process's start to the window's: loading, the world
and weights, the program's own set-up, compilation and the checked
first units of work."""


def read(run):
    return run["setup_s"]
