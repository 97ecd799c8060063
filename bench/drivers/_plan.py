"""The checks of each round's plan that every driver shares.

A driver keeps, for each checked round, the schedule, the assignment and
the allocation (b, f) that the program chose (``sched``, ``assign``,
``b``, ``f``). Against the reference they give:

- ``alloc_gap``: the share of the saving that problem (27)'s optimum,
  solved afresh by the reference in float64, makes over the paper's
  uniform baseline (each edge's band split evenly, f = f_max) which
  (b, f) leaves unmade, summed over every edge of the checked rounds;
- ``assign_gap``: the widest gap by which the assigner's own score of
  the edge a device was given lies below its best edge's, over the
  median spread of the scores (the float64 agent's Q for ``drl``, minus
  the distance for ``geo``);
- ``plan_violations``: the plan's broken guarantees (``reference.
  plan_violations``).

``kind`` puts something else in the program's place: ``control`` the
reference's own plan at bfloat16 (its optimal (b, f), and the argmax of
scores worked out from bfloat16 inputs), ``alloc0`` the allocation that
a solver left at its start reports (the band split evenly, f = f_max
sigmoid(1)), ``assign0`` every device on the edge after its own.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import reference

KINDS = ("program", "control", "half", "alloc0", "assign0")


def bf16(x):
    """``x`` rounded to bfloat16, back in float64."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def optimum(d):
    """The reference's optimal allocation of each checked round."""
    s = d.config["system"]
    return [reference.optimal_allocation(s, d.w.fleet, r["sched"],
                                         r["assign"], d.model_bits)
            for r in d.rounds]


def scores(d, sched, rounding=np.asarray):
    """(H, M) scores the assigner maximises, from ``rounding``'s inputs."""
    fleet = d.w.fleet
    if d.assigner == "drl":
        return reference.d3qn_q(jax.tree.map(rounding, d.drl),
                                rounding(reference.d3qn_features(fleet,
                                                                 sched)))
    if d.assigner == "geo":
        return -rounding(reference.edge_distances(fleet, sched))
    raise ValueError(f"no reference for assigner {d.assigner!r}")


def numbers(d, kind):
    """alloc_gap, assign_gap and plan_violations over the checked
    rounds, for what the program chose or for ``kind`` in its place."""
    if kind not in KINDS:
        raise ValueError(f"unknown check {kind!r}")
    if d.best is None:
        d.best = optimum(d)
    s, fleet, M = d.config["system"], d.w.fleet, len(d.w.fleet.B_m)
    unmade = saving = 0.0
    assign, bad = [], 0
    for r, (J, b_opt, f_opt) in zip(d.rounds, d.best):
        sched, chosen, b, f = r["sched"], r["assign"], r["b"], r["f"]
        if kind == "control":
            b, f = bf16(b_opt), bf16(f_opt)
            chosen = scores(d, sched, bf16).argmax(axis=1)
        elif kind == "alloc0":
            b, f = reference.even_allocation(
                s, fleet, chosen, s["f_max"] / (1.0 + np.exp(-1.0)))
        elif kind == "assign0":
            chosen = (chosen + 1) % M

        def objective(b, f):
            return reference.edge_objectives(s, fleet, sched, r["assign"],
                                             b, f, d.model_bits)

        unmade += np.nansum(objective(b, f) - J)
        saving += np.nansum(objective(*reference.even_allocation(
            s, fleet, r["assign"], s["f_max"])) - J)
        assign.append(reference.choice_gap(scores(d, sched), chosen))
        bad += reference.plan_violations(s, fleet, sched, r["assign"], b, f)
    return {"alloc_gap": float(unmade / saving), "assign_gap": max(assign),
            "plan_violations": float(bad)}
