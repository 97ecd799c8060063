"""Synchronous rounds of ``HFLFramework.run_round``, back to back.

A closed loop: the next global round starts when the last one's
evaluation is back. Set-up builds one framework from the seed (IKC
clustering of the whole fleet included), gives it the benchmark's
weights, and runs the first ``check_rounds`` rounds through
``run_round`` itself, keeping what each produced; the window then goes
on with the same object. After the window the reference follows those
first rounds from the same weights, with the schedule and assignment
that the program chose, and the numbers in ``check`` compare the two;
the plan itself (assignment and allocation) is checked by ``_plan``.

The cohort size is the configuration's ``H``. Cell parameters
(``params`` of the cell file): scheduler, assigner, alloc_steps,
check_rounds, block (devices per reference block).
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, reference, world as wd
from bench.drivers import _plan

HOST_SPANS = ("scheduler.schedule", "assigner.assign", "round_step",
              "eval_fn")


class Driver:
    host_spans = HOST_SPANS

    def __init__(self, config, params, seed):
        from repro.core import framework as fwm
        t0 = time.perf_counter()
        self.config, self.params, self.seed = config, params, seed
        self.w = wd.build_world(config, seed)
        sp = program.system_params(config)
        cfg = fwm.FrameworkConfig(
            scheduler=params["scheduler"], assigner=params["assigner"],
            H=config["H"], K=config["training"]["K"],
            lr=config["training"]["lr"], alloc_steps=params["alloc_steps"],
            seed=wd.sub_seed(seed, 5))
        drl = (program.d3qn_weights(config, seed)
               if params["assigner"] == "drl" else None)
        self.assigner = params["assigner"]
        self.drl = None if drl is None else program.host(drl)
        self.round_step = program.Recorder(fwm, "round_step", "round_step",
                                           _alloc)
        self.recorders = [self.round_step]
        fw = fwm.HFLFramework(sp, program.population(config, self.w.fleet),
                              program.federated(config, self.w), cfg,
                              drl_params=drl)
        fw.model_params = program.model_weights(config, seed)
        fw.scheduler.schedule = program.spanned("scheduler.schedule",
                                                fw.scheduler.schedule)
        fw.assigner.assign = program.spanned("assigner.assign",
                                             fw.assigner.assign)
        fw.spec = dataclasses.replace(
            fw.spec, eval_fn=program.spanned("eval_fn", fw.spec.eval_fn))
        self.fw, self.i, self.ref, self.best = fw, 0, None, None
        self.model_bits = float(fw.model_bits)
        self.p0 = program.host(fw.model_params)
        self.rounds = []
        t1 = time.perf_counter()
        self.round_step.on = True
        for _ in range(params["check_rounds"]):
            rec = self.unit()
            b, f = self.round_step.calls[-1]
            self.rounds.append({
                "sched": np.asarray(fw.last_sched),
                "assign": np.asarray(fw.last_assign), "b": b, "f": f,
                "T_i": rec["T_i"], "E_i": rec["E_i"],
                "params": program.host(fw.model_params)})
        self.round_step.on = False
        self.setup_parts = {"build_s": t1 - t0,
                            "check_units_s": time.perf_counter() - t1}

    def unit(self):
        """One global round; returns what the metrics read."""
        self.i += 1
        rec = self.fw.run_round(self.i)
        jax.block_until_ready(self.fw.model_params)
        return {"assign_s": rec["assign_latency_s"], "T_i": rec["T_i"],
                "E_i": rec["E_i"], "acc": rec["acc"],
                "samples": float(self.w.fleet.D[self.fw.last_sched].sum())}

    def free(self):
        """Drop the program's state before the reference runs."""
        self.round_step.restore()
        self.round_step.last = None
        del self.fw
        gc.collect()

    def check(self, kind="program"):
        """The numbers compared with their limits, for what the program
        produced (``kind="program"``) or for a changed reference put in
        its place: ``"control"`` runs it in bfloat16, ``"half"`` leaves
        out half of the cohort and averages over the rest; ``"alloc0"``
        and ``"assign0"`` change the plan alone (see ``_plan``)."""
        plan = _plan.numbers(self, kind)
        if self.ref is None:
            self.ref = self.follow(jnp.float32)
        got = [r["params"] for r in self.rounds]
        costs = [(r["T_i"], r["E_i"]) for r in self.rounds]
        if kind == "control":
            got = self.follow(jnp.bfloat16)
            costs = [self.cost(r, jnp.bfloat16) for r in self.rounds]
        elif kind == "half":
            got = self.follow(jnp.float32, half=True)
        return {**numbers(self, got, self.ref, costs), **plan}

    def follow(self, dtype, half=False):
        """The reference's parameters after each checked round."""
        s, w, out = self.config["system"], self.w, []
        p = jax.tree.map(lambda a: jnp.asarray(a, dtype), self.p0)
        with reference.precision(dtype):
            for r in self.rounds:
                sched = r["sched"]
                sizes = w.fleet.D[sched].astype(np.float32)
                if half:
                    sizes[len(sizes) // 2:] = 0.0
                p = reference.hfl_round(
                    p, jnp.asarray(w.X[sched]), jnp.asarray(w.y[sched]),
                    jnp.asarray(w.mask[sched]), jnp.asarray(sizes),
                    jnp.asarray(r["assign"]), M=s["n_edges"], L=s["L"],
                    Q=s["Q"], lr=self.config["training"]["lr"],
                    block=self.params["block"])
                out.append(program.host(p))
        return out

    def cost(self, r, dtype=np.float64):
        return reference.round_cost(self.config["system"], self.w.fleet,
                                    r["sched"], r["assign"], r["b"], r["f"],
                                    self.model_bits, dtype)


def _alloc(args, kwargs, out):
    """(b, f) of a ``round_step`` result, on the host."""
    _, (_, _, _, _, b, f) = out
    return np.asarray(b, np.float64), np.asarray(f, np.float64)


def numbers(d, got, ref, costs):
    """Each checked number of a synchronous cell."""
    cost = max(reference.rel_gap(c, d.cost(r))
               for c, r in zip(costs, d.rounds))
    return {
        "loss_gap": reference.loss_gap(got, ref, d.w.X_test, d.w.y_test),
        "update_gap": reference.norm_gap(reference.tree_sub(got[0], d.p0),
                                         reference.tree_sub(ref[0], d.p0)),
        "change_gap": reference.norm_gap(reference.tree_sub(got[-1], d.p0),
                                         reference.tree_sub(ref[-1], d.p0)),
        "cost_gap": cost,
    }
