"""The streaming service: ``AsyncHFLEngine.step_round`` back to back.

The loop ``launch/serve.run_serve`` runs, with no checkpoints: each
round schedules the cohort, prices it, and runs a discrete-event
simulation on the engine's virtual clock in which devices come and go
with the fleet's traffic. Every dispatch retrains the whole H-lane
cohort under a mask; every edge flushes its staleness-weighted buffer Q
times; the cloud aggregates; the round ends with an evaluation. The
fleet's availability is the benchmark's own, drawn from the seed.

Set-up runs the first ``check_rounds`` rounds through ``step_round``
itself and keeps, in order, what each dispatch and flush was given;
after the window the reference works out each flush's staleness from
that order alone, replays the events from the same weights, and the
numbers in ``check`` compare the two; the plan itself (the nearest-edge
assignment and the allocation) is checked by ``_plan``.

The fleet, its availability and the program's own seed (which draws the
cohorts) come from ``fleet_seed``; the run seed draws the data and the
weights. Every seed then replays the same fleet's day with the same
cohorts, so the work of a window does not swing with the seed: with
cohorts drawn per seed, updates_per_s spread by 20% over six seeds.

The cohort size is the configuration's ``H``. Cell parameters:
scheduler, buffer_size (null: wait for every member in flight),
staleness_exp, alloc_steps, check_rounds, block (lanes per
reference call), fleet_seed, horizon_s, and the traffic group read by
``world.availability``.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, reference, world as wd
from bench.drivers import _plan

HOST_SPANS = ("step_round", "scheduler.schedule", "assigner.assign",
              "_train_dispatched", "_flush_edge", "eval_fn")


class Driver:
    host_spans = HOST_SPANS

    def __init__(self, config, params, seed):
        from repro.core import async_engine as ae
        from repro.core import cost_model as cm
        t0 = time.perf_counter()
        self.config, self.params, self.seed = config, params, seed
        fleet_seed = params["fleet_seed"]
        self.w = wd.build_world(config, seed, fleet_seed)
        N = config["system"]["n_devices"]
        init_up, toggles = wd.availability(params["traffic"], N,
                                           wd.sub_seed(fleet_seed, 8),
                                           params["horizon_s"])
        trace = cm.AvailabilityTrace(init_up=init_up, toggles=toggles,
                                     latency_scale=np.ones(N))
        cfg = ae.AsyncConfig(
            H=config["H"], scheduler=params["scheduler"],
            K=config["training"]["K"],
            staleness_exp=params["staleness_exp"],
            buffer_size=params["buffer_size"], lr=config["training"]["lr"],
            alloc_steps=params["alloc_steps"],
            seed=wd.sub_seed(fleet_seed, 5))
        self.events = []
        self.recorders = [
            program.Recorder(ae, "_train_dispatched", "_train_dispatched",
                             _dispatch, self.events),
            program.Recorder(ae, "_flush_edge", "_flush_edge", _flush,
                             self.events),
            program.Recorder(ae, "_cloud_agg", "_cloud_agg", _cloud,
                             self.events),
            program.Recorder(ae, "evaluate_in_batches", "eval_fn",
                             lambda a, k, o: None)]
        eng = ae.AsyncHFLEngine(program.system_params(config),
                                program.population(config, self.w.fleet),
                                program.federated(config, self.w), cfg,
                                trace=trace)
        eng.model_params = program.model_weights(config, seed)
        eng.scheduler.schedule = program.spanned("scheduler.schedule",
                                                 eng.scheduler.schedule)
        eng.assigner.assign = program.spanned("assigner.assign",
                                              eng.assigner.assign)
        self.eng, self.ref, self.best = eng, None, None
        self.assigner, self.drl = "geo", None
        self.model_bits = float(eng.uplink_bits)
        self.p0 = program.host(eng.model_params)
        self.rounds = []
        t1 = time.perf_counter()
        for r in self.recorders:
            r.on = True
        for _ in range(params["check_rounds"]):
            del self.events[:]
            self.unit()
            b, f, tc, ec = (np.asarray(a, np.float64)
                            for a in eng.last_alloc)
            sched = np.asarray(eng.last_sched)
            self.rounds.append({
                "sched": sched, "assign": np.asarray(eng.last_assign),
                "b": b, "f": f, "tc": tc, "ec": ec,
                "events": [e for e in self.events if e[0] != "cloud"],
                "edge": [e for e in self.events if e[0] == "cloud"][0][1],
                "params": program.host(eng.model_params)})
        for r in self.recorders:
            r.on = False
        self.setup_parts = {"build_s": t1 - t0,
                            "check_units_s": time.perf_counter() - t1}

    def unit(self):
        """One streaming round; returns what the metrics read."""
        with jax.profiler.TraceAnnotation("step_round"):
            rec = self.eng.step_round(collect_eval=True)
        jax.block_until_ready(self.eng.model_params)
        sched = self.eng.last_sched
        return {"updates": rec["n_updates"], "aborted": rec["n_aborted"],
                "stale": rec["n_stale"], "acc": rec["acc"],
                "mean_d": float(self.w.fleet.D[sched].mean())}

    def free(self):
        for r in self.recorders:
            r.restore()
            r.last = None
        del self.eng
        gc.collect()

    def check(self, kind="program"):
        """The numbers compared with their limits, for what the program
        produced or, as in the synchronous driver, for the bfloat16
        ``"control"``, the ``"half"`` cohort fault, or a plan fault
        (``"alloc0"``, ``"assign0"``) in its place."""
        plan = _plan.numbers(self, kind)
        if self.ref is None:
            self.ref = self.replay(jnp.float32)
        got = [(r["params"], r["edge"]) for r in self.rounds]
        costs = [(r["tc"], r["ec"]) for r in self.rounds]
        if kind == "control":
            got = self.replay(jnp.bfloat16)
            costs = [self.cost(r, jnp.bfloat16) for r in self.rounds]
        elif kind == "half":
            got = self.replay(jnp.float32, half=True)
        return {**numbers(self, got, self.ref, costs), **plan}

    def replayed(self, r):
        """The round's events with each flush's staleness as the
        reference works it out from their order, and the number of
        flushes' members whose recorded staleness or order breaks it."""
        M = self.config["system"]["n_edges"]
        rows, broken = reference.staleness(r["events"], r["assign"], M)
        rows, out = iter(rows), []
        for ev in r["events"]:
            if ev[0] == "flush":
                stal = next(rows)
                broken += int(np.sum(ev[2] & (ev[4] != stal)))
                ev = ev[:4] + (stal,) + ev[5:]
            out.append(ev)
        return out, broken

    def replay(self, dtype, half=False):
        """The reference's (global, edge) parameters of each checked
        round, from the recorded events."""
        s, w, out = self.config["system"], self.w, []
        p = jax.tree.map(lambda a: jnp.asarray(a, dtype), self.p0)
        with reference.precision(dtype):
            for r in self.rounds:
                sched = r["sched"]
                sizes = w.fleet.D[sched].astype(np.float32)
                if half:
                    sizes[len(sizes) // 2:] = 0.0
                p, edge = reference.stream_round(
                    p, w.X[sched], w.y[sched], w.mask[sched], sizes,
                    r["assign"], self.replayed(r)[0], M=s["n_edges"],
                    L=s["L"],
                    lr=self.config["training"]["lr"],
                    block=self.params["block"])
                out.append((program.host(p), program.host(edge)))
        return out

    def cost(self, r, dtype=np.float64):
        return reference.device_costs(self.config["system"], self.w.fleet,
                                      r["sched"], r["assign"], r["b"],
                                      r["f"], self.model_bits, dtype)


def _dispatch(args, kwargs, out):
    return ("dispatch", np.asarray(args[4]))


def _flush(args, kwargs, out):
    _, _, m, delivered, members, _, staleness, a = args
    return ("flush", int(m), np.asarray(delivered), np.asarray(members),
            np.asarray(staleness), float(a))


def _cloud(args, kwargs, out):
    return ("cloud", program.host(args[0]))


def numbers(d, got, ref, costs):
    """Each checked number of a streaming cell."""
    cost = max(max(reference.rel_gap(c[0], rc[0]),
                   reference.rel_gap(c[1], rc[1]))
               for c, rc in zip(costs, (d.cost(r) for r in d.rounds)))
    return {
        "loss_gap": reference.loss_gap([g for g, _ in got],
                                       [r for r, _ in ref],
                                       d.w.X_test, d.w.y_test),
        "update_gap": reference.norm_gap(
            reference.tree_sub(got[0][0], d.p0),
            reference.tree_sub(ref[0][0], d.p0)),
        "edge_gap": reference.norm_gap(
            reference.tree_sub(got[0][1], d.p0),
            reference.tree_sub(ref[0][1], d.p0)),
        "change_gap": reference.norm_gap(
            reference.tree_sub(got[-1][0], d.p0),
            reference.tree_sub(ref[-1][0], d.p0)),
        "cost_gap": cost,
        "event_errors": float(sum(d.replayed(r)[1] for r in d.rounds)),
    }
