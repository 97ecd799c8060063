"""Share of the token-steps of the window's local training that are
padding: 1 - valid / padded, from the rounds' own counters
``tokens_valid`` and ``tokens_padded`` (None where the program keeps
no such counter)."""


def read(run):
    units = run["units"]
    if any(u.get("tokens_padded") is None for u in units):
        return None
    return 100.0 * (1.0 - sum(u["tokens_valid"] for u in units)
                    / sum(u["tokens_padded"] for u in units))
