"""Useful training operations of the window's rounds over the window and
the chip's bf16 peak: each round's valid token-steps (its
``tokens_valid`` counter, or the cohort's valid sequences times the
sequence length, L and Q where the program keeps no counter) at
``bench/flops_lm.py``'s count per token."""
from bench import flops_lm, reference_lm


def read(run):
    if run["peaks"] is None:
        return None
    config = run["config"]
    s, seq = config["system"], config["data"]["seq_len"]
    tokens = sum(u["tokens_valid"] if u.get("tokens_valid") is not None
                 else u["samples"] * seq * s["L"] * s["Q"]
                 for u in run["units"])
    ops = flops_lm.train_flops(reference_lm.dims(config), seq, tokens)
    return 100.0 * ops / run["window_s"] / (
        run["peaks"]["flops_bf16"] * run["chips"])
