"""Spans, named scopes and counters inside the HFL round and the async
engine, at tiny sizes on the CPU.

The fused round program carries the named scopes ``allocate``,
``local_train`` and ``aggregate``; ``run_round`` and ``step_round``
write host spans (``hfl.*``, ``async.*``, ``eval.*``) into a running
``jax.profiler`` trace; the round records count the cohort's
padding share and the lanes each async dispatch sends out and trains.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import async_engine as ae
from repro.core import compression as comp
from repro.core.framework import FrameworkConfig, HFLFramework, round_step

H = 6
SCOPES = ("allocate", "local_train", "aggregate")


@pytest.fixture(scope="module")
def world(small_world):
    sp, pop, fed = small_world
    return dataclasses.replace(sp, L=2, Q=2), pop, fed


@pytest.fixture(scope="module")
def fw(world):
    return HFLFramework(*world, FrameworkConfig(
        scheduler="fedavg", assigner="geo", H=H, alloc_steps=20, seed=0))


@pytest.fixture(scope="module")
def first_round(fw):
    return fw.run_round(1)


# ------------------------------------------------------- device scopes

@pytest.mark.parametrize("codec", ["none", "int8"])
@pytest.mark.parametrize("agg_kernel", [False, True])
def test_round_step_program_carries_the_layer_scopes(fw, agg_kernel, codec):
    pop, sp = fw.pop, fw.sp
    sched = np.arange(H)
    kw = dict(M=pop.n_edges, L=sp.L, Q=sp.Q, alloc_steps=20,
              agg_kernel=agg_kernel)
    cc = comp.CompressionConfig(codec=codec)
    kw.update(codec=cc, codec_key=comp.round_key(cc, 0, 1))
    state = (fw.model_params,
             (comp.init_state(cc, fw.model_params, H),
              comp.init_state(cc, fw.model_params, pop.n_edges)))
    hlo = round_step.lower(
        fw.apply_fn, sp, state, pop.u[sched], pop.D[sched],
        pop.p[sched], pop.g[sched], pop.g_cloud, pop.B_m, fw.X[sched],
        fw.y[sched], fw.mask[sched], pop.D[sched],
        jnp.asarray(sched % pop.n_edges), 0.01, **kw).compile().as_text()
    # full name stacks of the program's instructions (a reduction's
    # combiner keeps a name relative to its caller: left out)
    names = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
             if n.startswith("jit(round_step)/")]
    scoped = {s: [n for n in names if f"/{s}/" in n] for s in SCOPES}
    assert all(scoped.values()), {s: len(v) for s, v in scoped.items()}
    # outside the three layers: the loop over edge iterations and the
    # broadcast of the global model to the edges
    rest = [n for n in names if not any(f"/{s}/" in n for s in SCOPES)]
    assert len(rest) < 0.05 * len(names), rest
    assert any("/allocate/" in n and "/while/" in n for n in names)
    kernels = [n for n in names if "masked_aggregate" in n
               or "masked_decode_aggregate" in n]
    assert bool(kernels) == agg_kernel
    assert all("/aggregate/" in n for n in kernels)


# ---------------------------------------------------- round counters

@pytest.mark.parametrize("engine", ["fused", "sequential"])
def test_pad_share_is_the_cohorts_padding(world, fw, first_round, engine):
    if engine == "fused":
        run, rec = fw, first_round
    else:
        run = HFLFramework(*world, FrameworkConfig(
            scheduler="fedavg", assigner="geo", H=H, alloc_steps=20,
            seed=0, engine="sequential"))
        rec = run.run_round(1)
    sizes = np.array([len(y) for y in run.fed.y])
    D = sizes[run.last_sched]
    expect = 1.0 - D.sum() / (len(D) * sizes.max())
    assert rec["pad_share"] == pytest.approx(expect, abs=1e-12)
    assert 0.0 < rec["pad_share"] < 1.0


def test_run_round_leaves_the_round_program_running(fw, monkeypatch):
    """The spans and counters add no wait: the round program is only
    dispatched, and the evaluation's first chunk is what waits for it."""
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(1) or real(x))
    fw.run_round(3)
    assert waits == []


@pytest.mark.parametrize("buffer_size,codec", [(None, "none"), (1, "none"),
                                               (1, "int8")])
def test_dispatch_counters_match_the_dispatch_masks(world, monkeypatch,
                                                    buffer_size, codec):
    real, lanes = ae._train_dispatched, []

    def counting(*args, **kwargs):
        lanes.append(int(np.asarray(args[4]).sum()))    # dispatch_mask
        return real(*args, **kwargs)

    monkeypatch.setattr(ae, "_train_dispatched", counting)
    eng = ae.AsyncHFLEngine(*world, ae.AsyncConfig(
        H=H, alloc_steps=20, seed=0, buffer_size=buffer_size,
        compression=comp.CompressionConfig(codec=codec)))
    rec = eng.step_round(collect_eval=False)
    assert rec["n_dispatches"] == len(lanes) > 1
    assert rec["lanes_dispatched"] == sum(lanes)
    occupancy = rec["lanes_dispatched"] / (rec["n_dispatches"] * rec["H"])
    # the first dispatch sends out the whole cohort, every later one only
    # the members an edge flush sends back out
    assert lanes[0] == H
    assert 0.0 < occupancy < 1.0
    # each dispatch trains its lanes in whole chunks of C
    C = ae.dispatch_chunk(rec["H"])
    assert rec["lanes_trained"] == sum(-(-k // C) * C for k in lanes)
    assert (rec["lanes_dispatched"] <= rec["lanes_trained"]
            < rec["lanes_dispatched"] + C * rec["n_dispatches"])


# ------------------------------------------------------- host spans

def _program_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of the program's host spans."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in data.planes for line in plane.lines
            for ev in line.events
            if ev.name.startswith(("hfl.", "async.", "eval."))]


SYNC_SPANS = ("hfl.schedule", "hfl.assign", "hfl.cohort", "hfl.round_step",
              "hfl.eval", "hfl.record")
ASYNC_SPANS = ("async.schedule", "async.assign", "async.price",
               "async.dispatch", "async.flush", "async.cloud_agg",
               "async.eval")


@pytest.mark.parametrize("engine", ["sync", "async"])
def test_a_profiled_round_holds_each_span_nested_with_its_round(
        world, fw, first_round, engine, tmp_path):
    if engine == "sync":
        run, names, outer = (lambda: fw.run_round(2)), SYNC_SPANS, "hfl.eval"
    else:
        eng = ae.AsyncHFLEngine(*world, ae.AsyncConfig(
            H=H, alloc_steps=20, seed=0))
        eng.step_round(collect_eval=True)           # compile outside
        run, names, outer = eng.step_round, ASYNC_SPANS, "async.eval"
    with jax.profiler.trace(str(tmp_path)):
        run()
    spans = _program_spans(str(tmp_path))
    assert {name for name, *_ in spans} == set(names) | {"eval.upload",
                                                         "eval.wait"}
    for name, _, _, stats in spans:      # the profiled round is round 2
        if not name.startswith("eval."):
            assert stats.get("round") == 2, name
    (lo, hi), = [(s, e) for n, s, e, _ in spans if n == outer]
    evals = [(s, e) for n, s, e, _ in spans if n.startswith("eval.")]
    # one upload and one wait per test chunk, all inside the round's eval
    assert len(evals) % 2 == 0 and evals
    assert all(lo <= s <= e <= hi for s, e in evals)
