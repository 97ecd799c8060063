"""Synchronous rounds of ``HFLFramework.run_round`` over a LoRA payload.

The sync driver's scheme (``sync.py``) for federated LoRA fine-tuning
of a frozen decoder: the configuration names the program's payload
(``program``: registry arch and scale) and gives the model's published
keys, from which the benchmark makes the weights in the reference's
layout (``reference_lm``) and hands them to the program in its own
(``program_lm``): the payload's spec is handed to ``HFLFramework``
with the base, held in bfloat16, as its frozen tree and the adapters
and head as its trainable tree, so the program draws neither. The
first ``check_rounds`` rounds run through ``run_round`` in set-up and
are kept; after the window, with the program freed, the reference
follows them from the same weights with the schedule and assignment the
program chose, ``block`` devices at a time. The plan is checked by
``_plan`` as in every driver.

Unit records add ``tokens_valid`` and ``tokens_padded`` (the round's
counters) for ``pad_share.lora`` and ``mfu.lora``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, program_lm, reference, reference_lm
from bench import world as wd
from bench import world_lm
from bench.drivers import _plan, sync

HOST_SPANS = sync.HOST_SPANS
BASE_DTYPE = jnp.bfloat16     # the configuration's base precision


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _base(key, m, dtype):
    """The base from ``key`` in the reference's layout, in the dtypes
    the program holds it in; with ``dtype`` below float32, all in it.
    The float32 reference computes on these values in float32."""
    w = program_lm.held(reference_lm.init(key, m), BASE_DTYPE)
    if dtype == jnp.float32:
        return w
    return jax.tree.map(lambda a: a.astype(dtype), w)


@functools.partial(jax.jit, static_argnames=("m",))
def _program_base(key, m):
    """The same base, laid out as the program holds it."""
    w = program_lm.held(reference_lm.init(key, m), BASE_DTYPE)
    return program_lm.frozen(w, m, BASE_DTYPE)


class Driver:
    host_spans = HOST_SPANS

    def __init__(self, config, params, seed):
        from repro.configs.registry import get_hfl_spec
        from repro.core import framework as fwm
        t0 = time.perf_counter()
        # the program's payload at the configuration's scale (first: a
        # program without it fails here, before any work)
        spec = get_hfl_spec(config["program"]["arch"],
                            config["program"]["scale"])
        self.config, self.params, self.seed = config, params, seed
        self.m = reference_lm.dims(config)
        self.w = world_lm.build_world(config, seed)
        self.key = jax.random.PRNGKey(wd.sub_seed(seed, 6))
        sp = program.system_params(config)
        cfg = fwm.FrameworkConfig(
            arch=config["program"]["arch"],
            scheduler=params["scheduler"], assigner=params["assigner"],
            H=config["H"], K=config["training"]["K"],
            lr=config["training"]["lr"], alloc_steps=params["alloc_steps"],
            seed=wd.sub_seed(seed, 5))
        drl = (program.d3qn_weights(config, seed)
               if params["assigner"] == "drl" else None)
        self.assigner = params["assigner"]
        self.drl = None if drl is None else program.host(drl)
        self.round_step = program.Recorder(fwm, "round_step", "round_step",
                                           sync._alloc)
        self.recorders = [self.round_step]
        self.ad0 = reference_lm.adapters_init(
            jax.random.PRNGKey(wd.sub_seed(seed, 8)), self.m,
            config["training"]["adapter_b_std"])
        # the benchmark's base and adapters in place of the payload's
        # own initialisation, which is never drawn
        base = _program_base(self.key, self.m)
        trainable = program_lm.params(self.ad0, self.m)
        spec = dataclasses.replace(
            spec, frozen_init_fn=lambda key, fed: base,
            init_fn=lambda key, fed: trainable,
            eval_fn=program.spanned("eval_fn", spec.eval_fn))
        fw = fwm.HFLFramework(sp, program.population(config, self.w.fleet),
                              program.federated(config, self.w), cfg,
                              drl_params=drl, spec=spec)
        # the program prices what it trains: the reference's own count
        n_train = sum(a.size for a in jax.tree.leaves(self.ad0))
        if fw.model_bits != 32 * n_train:
            raise ValueError(f"program prices {fw.model_bits} bits; the "
                             f"adapters and head are {32 * n_train}")
        fw.scheduler.schedule = program.spanned("scheduler.schedule",
                                                fw.scheduler.schedule)
        fw.assigner.assign = program.spanned("assigner.assign",
                                             fw.assigner.assign)
        self.fw, self.i, self.ref, self.best = fw, 0, None, None
        self.model_bits = float(fw.model_bits)
        self.ad0 = program.host(self.ad0)
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
                "params": program_lm.adapters(fw.model_params, self.m)})
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
                "samples": float(self.w.fleet.D[self.fw.last_sched].sum()),
                "tokens_valid": rec.get("tokens_valid"),
                "tokens_padded": rec.get("tokens_padded")}

    def free(self):
        """Drop the program's state before the reference runs."""
        self.round_step.restore()
        self.round_step.last = None
        del self.fw
        gc.collect()

    def check(self, kind="program"):
        """The numbers compared with their limits (see ``sync.Driver``)."""
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
        """The reference's adapters after each checked round."""
        s, w, out = self.config["system"], self.w, []
        base = _base(self.key, self.m, dtype)
        ad = jax.tree.map(lambda a: jnp.asarray(a, dtype), self.ad0)
        with reference.precision(dtype):
            for r in self.rounds:
                sched = r["sched"]
                sizes = w.fleet.D[sched].astype(np.float32)
                if half:
                    sizes[len(sizes) // 2:] = 0.0
                ad = reference_lm.hfl_round(
                    base, ad, jnp.asarray(w.X[sched]),
                    jnp.asarray(w.y[sched]), jnp.asarray(w.mask[sched]),
                    jnp.asarray(sizes), jnp.asarray(r["assign"]),
                    M=s["n_edges"], L=s["L"], Q=s["Q"],
                    lr=self.config["training"]["lr"],
                    block=self.params["block"], m=self.m)
                out.append(program.host(ad))
        del base
        return out

    def cost(self, r, dtype=np.float64):
        return reference.round_cost(self.config["system"], self.w.fleet,
                                    r["sched"], r["assign"], r["b"], r["f"],
                                    self.model_bits, dtype)


def numbers(d, got, ref, costs):
    """Each checked number of the cell: as ``sync.numbers``, over the
    adapters and head, the test loss read with the float32 base."""
    base = _base(d.key, d.m, jnp.float32)
    X, y = d.w.X_test, d.w.y_test
    with reference.precision(jnp.float32):
        loss = max(
            abs(reference_lm.test_loss(base, g, X, y, d.m)
                - (r_loss := reference_lm.test_loss(base, r, X, y, d.m)))
            / r_loss for g, r in zip(got, ref))
    flat, p0 = reference_lm.flat, reference_lm.flat(d.ad0)
    return {
        "loss_gap": loss,
        "update_gap": reference.norm_gap(reference.tree_sub(flat(got[0]), p0),
                                         reference.tree_sub(flat(ref[0]), p0)),
        "change_gap": reference.norm_gap(
            reference.tree_sub(flat(got[-1]), p0),
            reference.tree_sub(flat(ref[-1]), p0)),
        "cost_gap": max(reference.rel_gap(c, d.cost(r))
                        for c, r in zip(costs, d.rounds)),
    }
