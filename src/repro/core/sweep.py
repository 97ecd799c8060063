"""Batched simulation sweeps over the fused round engine.

The paper's headline experiments (Figs. 3-7, Table II) are grids of
(scheduler x assigner x scheduling ratio x seed) cells, each a full
multi-round HFL simulation. Re-running ``HFLFramework`` per cell pays the
Python/dispatch overhead S times per round; ``SweepRunner`` instead
stacks S independent worlds (population + federated data) along a
leading lane axis and vmaps the traceable ``round_step_core`` over it
(``_sweep_round_lanes``), so every round of every lane is ONE jitted
dispatch. Scheduling ratios change the cohort shape H, so each ratio is
its own vmapped program (lanes within a ratio share one).

Three further dispatch layouts compose on top of the per-round vmap
(details in ``docs/engine.md``): ``shard=True`` block-shards the lane
axis over a 1-D device mesh via ``shard_map`` (``sweep_round_sharded``),
``lane_chunk=k`` executes lanes in sequential vmapped chunks (CPU
cache-blocking), and ``run(fused=True)`` folds the entire R-round sweep
— scheduling, assignment, eval and done-masks traced — into one
``lax.scan`` dispatch (``sweep_scan`` / ``sweep_scan_sharded``).

Semantics per lane match ``HFLFramework`` with ``engine="fused"``:
Algorithm-1 training weighted by the cost-model dataset sizes pop.D,
all-edges convex resource allocation, and round costs (13)/(14).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import compression as comp
from repro.core import cost_model as cm
from repro.core.assignment.drl import drl_assign_traced
from repro.core.assignment.geo import GeoAssigner, geo_assign_traced
from repro.core.assignment.hfel import hfel_search_traced
from repro.core.framework import round_step_core
from repro.core.hfl import hfl_global_iteration_core, pad_device_data
from repro.core.scheduling import (FedAvgScheduler, IKCScheduler,
                                   VKCScheduler, run_device_clustering)
from repro.core.scheduling.schedulers import TracedFedAvg, _topup
from repro.configs.registry import get_hfl_spec
from repro.data.partition import FederatedData
from repro.utils import tree_bytes


def build_scheduler(name: str, fed: FederatedData, sp: cm.SystemParams,
                    H: int, K: int = 10, lr: float = 0.01, seed: int = 0,
                    use_kernel: bool = False,
                    pop: Optional[cm.Population] = None,
                    arch: str = "hfl-cnn"):
    """Standalone scheduler construction (shared by benchmarks/sweeps).

    IKC clusters with the arch's auxiliary mini model ξ on its
    clustering crop, VKC with the full payload, FedAvg samples
    uniformly — mirroring ``HFLFramework._setup_scheduler`` without
    instantiating the whole framework. NOTE: the framework keeps its own
    copy because its key derivation and clustering-cost/ARI bookkeeping
    are part of its seeded record; if the clustering recipe changes,
    update BOTH. Both the full and the mini init take ``fed.n_classes``
    (an earlier revision silently defaulted to 10, mispricing
    ``compute_scale`` and clustering with the wrong logits head whenever
    ``n_classes != 10``).

    With ``pop`` given, returns (scheduler, clustering_stats) where
    clustering_stats carries the Table-II quantities (ari, delay_s,
    energy_j, aux_bits; empty dict for FedAvg); otherwise returns just
    the scheduler.
    """
    from repro.core.clustering import adjusted_rand_index
    from repro.core.scheduling.device_clustering import clustering_cost
    from repro.utils import tree_bytes as _tb

    if name == "fedavg":
        sched = FedAvgScheduler(fed.n_devices, H)
        return (sched, {}) if pop is not None else sched
    if name not in ("ikc", "vkc"):
        raise ValueError(f"unknown scheduler {name!r}")
    spec = get_hfl_spec(arch)
    key = jax.random.PRNGKey(seed)
    X, y, mask = pad_device_data(fed)
    h = max(1, H // K)
    full = spec.init_fn(key, fed)
    full_bits = _tb(full) * 8
    if name == "ikc":
        mini = spec.mini_init_fn(key, fed)
        crop = spec.mini_preprocess_fn(X, key)
        labels, _ = run_device_clustering(key, spec.mini_apply_fn, mini,
                                          crop, y, mask, K, sp.L, lr,
                                          use_kernel=use_kernel)
        sched = IKCScheduler(labels, h)
        aux_bits = _tb(mini) * 8
        compute_scale = aux_bits / max(1, full_bits)
    else:
        labels, _ = run_device_clustering(key, spec.apply_fn, full, X, y,
                                          mask, K, sp.L, lr,
                                          use_kernel=use_kernel)
        sched = VKCScheduler(labels, h)
        aux_bits = full_bits
        compute_scale = 1.0
    if pop is None:
        return sched
    delay, energy = clustering_cost(sp, pop, aux_bits,
                                    compute_scale=compute_scale)
    stats = {"ari": adjusted_rand_index(np.asarray(labels),
                                        fed.majority_class),
             "delay_s": delay, "energy_j": energy,
             "aux_bits": float(aux_bits)}
    return sched, stats


def _sweep_round_lanes(apply_fn, sp: cm.SystemParams, params_b, u_b, D_b,
                       p_b, g_b, g_cloud_b, B_m_b, X_b, y_b, mask_b,
                       sizes_b, sched_b, assign_b, lr, done_b, *, M: int,
                       L: int, Q: int, alloc_steps: int, train_only: bool,
                       agg_kernel: bool, lane_chunk: Optional[int] = None,
                       codec=None, codec_state_b=None, codec_keys_b=None):
    """Traceable lane-vmapped round body shared by the single-device
    ``sweep_round`` jit and the ``shard_map`` blocks of
    ``sweep_round_sharded`` (each device runs this on its lane block).

    lane_chunk: None vmaps the whole lane axis into one batched program
    (the PR-1 layout, right for MXU-rich hardware). An int processes the
    lanes sequentially in vmapped chunks of that size via ``lax.map`` —
    on CPU hosts the small per-chunk working set stays cache-resident
    and XLA stops batch-fusing the tiny per-lane ops into bandwidth-
    bound monsters, which measures 1.8-2.4x by itself at S=128 across
    runs (see ``BENCH_sweep_shard.json``); must divide the lane-axis
    length.

    With an active ``codec`` the compressed round engine runs instead:
    ``codec_state_b`` is ``(dev_resid (S, N, ...), edge_resid
    (S, M, ...))`` error-feedback trees (cohort rows gathered/scattered
    per lane, frozen on done lanes like the params), ``codec_keys_b``
    (S, 2) per-lane round keys, and the return gains a third element —
    the updated state. Inactive codec keeps the seed trace untouched.
    """
    codec_on = codec is not None and codec.active

    def one(params, u, D, p, g, g_cloud, B_m, X, y, mask, sizes, sched,
            assign, done, *cstate):
        if codec_on:
            dev_resid, edge_resid, ckey = cstate
            cohort_resid = jax.tree.map(lambda r: r[sched], dev_resid)
        if train_only:
            if codec_on:
                new_params, cohort_resid, new_edge_resid = \
                    hfl_global_iteration_core(
                        apply_fn, params, X[sched], y[sched], mask[sched],
                        sizes[sched], assign, M=M, L=L, Q=Q, lr=lr,
                        agg_kernel=agg_kernel, codec=codec,
                        dev_resid=cohort_resid, edge_resid=edge_resid,
                        codec_key=ckey)
            else:
                new_params = hfl_global_iteration_core(
                    apply_fn, params, X[sched], y[sched], mask[sched],
                    sizes[sched], assign, M=M, L=L, Q=Q, lr=lr,
                    agg_kernel=agg_kernel)
            zero = jnp.zeros(())
            T_i, E_i = zero, zero
        elif codec_on:
            new_params, (cohort_resid, new_edge_resid), \
                (T_i, E_i, _, _, _, _) = round_step_core(
                    apply_fn, sp, params, u[sched], D[sched], p[sched],
                    g[sched], g_cloud, B_m, X[sched], y[sched],
                    mask[sched], sizes[sched], assign, lr, M=M, L=L, Q=Q,
                    alloc_steps=alloc_steps, agg_kernel=agg_kernel,
                    codec=codec, codec_state=(cohort_resid, edge_resid),
                    codec_key=ckey)
        else:
            new_params, (T_i, E_i, _, _, _, _) = round_step_core(
                apply_fn, sp, params, u[sched], D[sched], p[sched],
                g[sched], g_cloud, B_m, X[sched], y[sched], mask[sched],
                sizes[sched], assign, lr, M=M, L=L, Q=Q,
                alloc_steps=alloc_steps, agg_kernel=agg_kernel)
        new_params = jax.tree.map(
            lambda old, new: jnp.where(done, old, new), params, new_params)
        costs = (jnp.where(done, 0.0, T_i), jnp.where(done, 0.0, E_i))
        if not codec_on:
            return new_params, costs
        freeze = functools.partial(
            jax.tree.map, lambda old, new: jnp.where(done, old, new))
        new_dev_resid = freeze(
            dev_resid, jax.tree.map(
                lambda full, nr: full.at[sched].set(nr), dev_resid,
                cohort_resid))
        return new_params, costs, (new_dev_resid,
                                   freeze(edge_resid, new_edge_resid))

    lane_in = (params_b, u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, X_b, y_b,
               mask_b, sizes_b, sched_b, assign_b, done_b)
    if codec_on:
        lane_in = lane_in + (codec_state_b[0], codec_state_b[1],
                             codec_keys_b)
    if lane_chunk is None:
        return jax.vmap(one)(*lane_in)
    n = sched_b.shape[0]
    if n % lane_chunk != 0:
        raise ValueError(f"lane_chunk={lane_chunk} must divide the lane "
                         f"axis ({n})")
    stacked = jax.tree.map(
        lambda x: x.reshape((n // lane_chunk, lane_chunk) + x.shape[1:]),
        lane_in)
    out = jax.lax.map(lambda xs: jax.vmap(one)(*xs), stacked)
    return jax.tree.map(
        lambda x: x.reshape((n,) + x.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=(
    "apply_fn", "sp", "M", "L", "Q", "alloc_steps", "train_only",
    "agg_kernel", "lane_chunk", "codec"))
def sweep_round(apply_fn, sp: cm.SystemParams, params_b, u_b, D_b, p_b,
                g_b, g_cloud_b, B_m_b, X_b, y_b, mask_b, sizes_b, sched_b,
                assign_b, lr, *, M: int, L: int, Q: int, alloc_steps: int,
                train_only: bool = False, agg_kernel: bool = False,
                lane_chunk: Optional[int] = None, done_b=None,
                codec=None, codec_state_b=None, codec_keys_b=None):
    """One fused round for S lanes at once.

    Population/data arrays carry a leading lane axis (S, ...); sched_b
    and assign_b are (S, H); sizes_b (S, N) holds the Algorithm-1
    aggregation weights. Gathers each lane's cohort and vmaps
    ``round_step_core``, returning (params_b, (T_i, E_i)) with (S,)
    cost vectors. train_only=True skips resource allocation and cost
    bookkeeping entirely (accuracy-only sweeps like Fig. 3/4) and
    returns zero costs. agg_kernel=True routes every lane's Algorithm-1
    aggregation through the lane-batched ``hier_agg`` Pallas kernel —
    the vmap hits the kernel's ``custom_vmap`` rule, so all S lanes
    share ONE (S, P/BP)-grid launch per aggregation instead of falling
    back to S per-lane interpret calls. done_b: optional (S,) bool mask
    of lanes that already reached the sweep's accuracy target — a done
    lane's model is frozen (params pass through unchanged) and it stops
    accruing training compute (its T_i/E_i come back zero), so finished
    lanes no longer distort the sweep's cost totals. lane_chunk: see
    ``_sweep_round_lanes`` — cache-blocked sequential chunks for CPU
    hosts, None (one vmapped program) for accelerators.
    """
    if done_b is None:
        done_b = jnp.zeros((sched_b.shape[0],), bool)
    return _sweep_round_lanes(
        apply_fn, sp, params_b, u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, X_b,
        y_b, mask_b, sizes_b, sched_b, assign_b, lr, done_b, M=M, L=L, Q=Q,
        alloc_steps=alloc_steps, train_only=train_only,
        agg_kernel=agg_kernel, lane_chunk=lane_chunk, codec=codec,
        codec_state_b=codec_state_b, codec_keys_b=codec_keys_b)


@functools.partial(jax.jit, static_argnames=(
    "apply_fn", "sp", "M", "L", "Q", "alloc_steps", "train_only",
    "agg_kernel", "mesh", "lane_chunk", "codec"))
def sweep_round_sharded(apply_fn, sp: cm.SystemParams, params_b, u_b, D_b,
                        p_b, g_b, g_cloud_b, B_m_b, X_b, y_b, mask_b,
                        sizes_b, sched_b, assign_b, lr, *, M: int, L: int,
                        Q: int, alloc_steps: int, mesh,
                        train_only: bool = False, agg_kernel: bool = False,
                        lane_chunk: Optional[int] = None, done_b=None,
                        codec=None, codec_state_b=None, codec_keys_b=None):
    """``sweep_round`` laid out over a 1-D ``Mesh(("lane",))``.

    Same args/semantics as ``sweep_round`` plus a static ``mesh``
    (``launch.mesh.sweep_mesh()``): the stacked lane axis S — which must
    be a multiple of the mesh's device count; ``SweepRunner`` pads with
    dead done-masked lanes — is block-partitioned over the devices and
    every device runs the identical vmapped round body on its S/d lane
    block as ONE SPMD program. Lanes are independent (no collectives):
    ``out_specs`` just re-stacks the per-device blocks. Scheduling /
    assignment stay host-side in ``SweepRunner.run`` — nothing inside
    the sharded region calls back to the host, which is what keeps the
    hfel/drl assignment hooks shard-compatible (their jitted searches
    run on the default device *between* sharded rounds). lane_chunk
    applies *within* each device's lane block (must divide S/d; see
    ``_sweep_round_lanes`` for when to use it).
    """
    if done_b is None:
        done_b = jnp.zeros((sched_b.shape[0],), bool)
    lane, rep = PartitionSpec("lane"), PartitionSpec()
    codec_on = codec is not None and codec.active

    def block(params_b, u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, X_b, y_b,
              mask_b, sizes_b, sched_b, assign_b, lr, done_b, *cstate):
        kw = {}
        if codec_on:
            kw = dict(codec=codec, codec_state_b=(cstate[0], cstate[1]),
                      codec_keys_b=cstate[2])
        return _sweep_round_lanes(
            apply_fn, sp, params_b, u_b, D_b, p_b, g_b, g_cloud_b, B_m_b,
            X_b, y_b, mask_b, sizes_b, sched_b, assign_b, lr, done_b,
            M=M, L=L, Q=Q, alloc_steps=alloc_steps, train_only=train_only,
            agg_kernel=agg_kernel, lane_chunk=lane_chunk, **kw)

    in_specs = (lane,) * 13 + (rep, lane)
    out_specs = (lane, (lane, lane))
    args = (params_b, u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, X_b, y_b,
            mask_b, sizes_b, sched_b, assign_b, lr, done_b)
    if codec_on:
        in_specs = in_specs + (lane, lane, lane)
        out_specs = (lane, (lane, lane), (lane, lane))
        args = args + (codec_state_b[0], codec_state_b[1], codec_keys_b)
    sharded = jax.shard_map(block, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return sharded(*args)


def _sweep_eval_lanes(apply_fn, params_b, Xt_b, yt_b):
    """Traceable lane-vmapped full-batch test accuracy — shared by the
    per-round ``_sweep_eval`` jit and the in-scan eval of the fused
    sweep (where it feeds the done-mask early-exit)."""
    return jax.vmap(
        lambda prm, Xt, yt: jnp.mean(
            (jnp.argmax(apply_fn(prm, Xt), axis=-1) == yt)
            .astype(jnp.float32))
    )(params_b, Xt_b, yt_b)


_sweep_eval = functools.partial(jax.jit, static_argnames=("apply_fn",))(
    _sweep_eval_lanes)


# ------------------------------------------------------------ fused scan

_HFEL_FUSED_DEFAULTS = dict(n_transfer=40, n_exchange=80, n_candidates=16,
                            warm_steps=None, accept_top=4)


def _sweep_scan_lanes(apply_fn, sp, sp_assign, params_b, u_b, D_b, p_b,
                      g_b, g_cloud_b, B_m_b, X_b, y_b, mask_b, sizes_b,
                      dev_pos_b, edge_pos_b, Xt_b, yt_b, sched_rs,
                      sched_state_b, assign_keys_b, done_b, drl_params, lr,
                      codec_state_b, codec_base_b, codec_r0,
                      *, M: int, L: int, Q: int, alloc_steps: int,
                      train_only: bool, agg_kernel: bool,
                      lane_chunk: Optional[int], assign: str, hfel_cfg,
                      target_acc: Optional[float], n_rounds: int,
                      traced_sched, codec=None):
    """Traceable R-round S-lane sweep body: ``lax.scan`` over rounds of
    (scheduler step -> traced assignment -> lane-vmapped round body ->
    in-scan eval -> done-mask update). Shared by the single-device
    ``sweep_scan`` jit and the ``shard_map`` blocks of
    ``sweep_scan_sharded``.

    Scheduling comes either from the precomputed ``sched_rs`` (R, S, H)
    tensor (host schedulers; ``traced_sched=None``) or, with a
    ``traced_sched`` ``TracedFedAvg``, from in-scan draws against the
    carried ``sched_state_b`` pytree (one PRNG key per lane).
    Assignment (``assign`` in mod|geo|drl|hfel) runs fully in-trace per
    round; hfel consumes one split of the carried ``assign_keys_b`` per
    round (split unconditionally for every assigner so the carry
    structure — and hence fused-vs-oracle parity — is mode-independent).
    The done-mask semantics mirror the host loop exactly: a lane's
    round outputs are recorded, then its done flag absorbs
    ``acc >= target_acc``, freezing it from the NEXT round on.

    Returns ((params_b, done_b, sched_state_b, assign_keys_b),
    (acc (R, S), T_i (R, S), E_i (R, S))).

    With an active ``codec`` the carry additionally holds the per-lane
    error-feedback state ``codec_state_b`` and a round counter (seeded
    at ``codec_r0``) — codec keys are re-derived in-scan as
    ``fold_in(codec_base_b[lane], round)``, the exact stream the host
    loop draws, so fused and host compressed sweeps stay in lockstep.
    """
    hfel_kw = dict(hfel_cfg) if hfel_cfg is not None else None
    codec_on = codec is not None and codec.active

    def assign_lane(u, D, p, g, g_cloud, B_m, dev_pos, edge_pos, sched,
                    key):
        if assign == "mod":
            return (sched % M).astype(jnp.int32)
        if assign == "geo":
            return geo_assign_traced(dev_pos, edge_pos, sched)
        if assign == "drl":
            return drl_assign_traced(drl_params, u, D, p, g, sched)
        a, _ = hfel_search_traced(
            sp_assign, u[sched], D[sched], p[sched], g[sched], B_m,
            g_cloud, key, alloc_steps=alloc_steps, **hfel_kw)
        return a

    def step(carry, xs):
        if codec_on:
            (params_b, done_b, sched_state_b, keys_b, codec_state_b,
             r) = carry
        else:
            params_b, done_b, sched_state_b, keys_b = carry
        if traced_sched is None:
            sched_b = xs
        else:
            sched_state_b, sched_b = jax.vmap(traced_sched.step)(
                sched_state_b)
        splits = jax.vmap(jax.random.split)(keys_b)        # (S, 2, 2)
        keys_b, sub_b = splits[:, 0], splits[:, 1]
        assign_b = jax.vmap(assign_lane)(
            u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, dev_pos_b, edge_pos_b,
            sched_b, sub_b)
        if codec_on:
            ckeys_b = jax.vmap(
                lambda k: jax.random.fold_in(k, r))(codec_base_b)
            new_params, (T_i, E_i), codec_state_b = _sweep_round_lanes(
                apply_fn, sp, params_b, u_b, D_b, p_b, g_b, g_cloud_b,
                B_m_b, X_b, y_b, mask_b, sizes_b, sched_b, assign_b, lr,
                done_b, M=M, L=L, Q=Q, alloc_steps=alloc_steps,
                train_only=train_only, agg_kernel=agg_kernel,
                lane_chunk=lane_chunk, codec=codec,
                codec_state_b=codec_state_b, codec_keys_b=ckeys_b)
        else:
            new_params, (T_i, E_i) = _sweep_round_lanes(
                apply_fn, sp, params_b, u_b, D_b, p_b, g_b, g_cloud_b,
                B_m_b, X_b, y_b, mask_b, sizes_b, sched_b, assign_b, lr,
                done_b, M=M, L=L, Q=Q, alloc_steps=alloc_steps,
                train_only=train_only, agg_kernel=agg_kernel,
                lane_chunk=lane_chunk)
        acc = _sweep_eval_lanes(apply_fn, new_params, Xt_b, yt_b)
        if target_acc is not None:
            done_b = done_b | (acc >= target_acc)
        if codec_on:
            return (new_params, done_b, sched_state_b, keys_b,
                    codec_state_b, r + 1), (acc, T_i, E_i)
        return (new_params, done_b, sched_state_b, keys_b), (acc, T_i, E_i)

    carry0 = (params_b, done_b, sched_state_b, assign_keys_b)
    if codec_on:
        carry0 = carry0 + (codec_state_b, codec_r0)
    xs = sched_rs if traced_sched is None else None
    return jax.lax.scan(step, carry0, xs,
                        length=n_rounds if xs is None else None)


_SCAN_STATICS = ("apply_fn", "sp", "sp_assign", "M", "L", "Q",
                 "alloc_steps", "train_only", "agg_kernel", "lane_chunk",
                 "assign", "hfel_cfg", "target_acc", "n_rounds",
                 "traced_sched", "codec")


@functools.partial(jax.jit, static_argnames=_SCAN_STATICS)
def sweep_scan(apply_fn, sp: cm.SystemParams, sp_assign, params_b, u_b,
               D_b, p_b, g_b, g_cloud_b, B_m_b, X_b, y_b, mask_b, sizes_b,
               dev_pos_b, edge_pos_b, Xt_b, yt_b, sched_rs, sched_state_b,
               assign_keys_b, done_b, drl_params, lr, codec_state_b=None,
               codec_base_b=None, codec_r0=None, *, M: int, L: int,
               Q: int, alloc_steps: int, train_only: bool = False,
               agg_kernel: bool = False, lane_chunk: Optional[int] = None,
               assign: str = "geo", hfel_cfg=None,
               target_acc: Optional[float] = None, n_rounds: int = 1,
               traced_sched=None, codec=None):
    """An R-round, S-lane sweep as ONE jitted dispatch.

    The whole-sweep analogue of ``sweep_round``: scheduling, assignment
    (including the traced HFEL K-candidate search and D3QN deployment),
    R rounds of the fused engine, per-round eval and the done-mask
    early-exit all live inside a single ``lax.scan`` — zero host
    round-trips between rounds. Population/data arrays as in
    ``sweep_round`` plus dev_pos_b/edge_pos_b (S, ·, 2) positions
    (traced geo) and Xt_b/yt_b test stacks (in-scan eval).
    ``sp_assign`` is the SystemParams the hfel objective scores with
    (the host path's assigner uses the un-patched sweep params, not the
    model-bits-patched round ``sp``). See ``_sweep_scan_lanes`` for the
    scheduling/assignment operand semantics and the carry layout.
    """
    return _sweep_scan_lanes(
        apply_fn, sp, sp_assign, params_b, u_b, D_b, p_b, g_b, g_cloud_b,
        B_m_b, X_b, y_b, mask_b, sizes_b, dev_pos_b, edge_pos_b, Xt_b,
        yt_b, sched_rs, sched_state_b, assign_keys_b, done_b, drl_params,
        lr, codec_state_b, codec_base_b, codec_r0,
        M=M, L=L, Q=Q, alloc_steps=alloc_steps, train_only=train_only,
        agg_kernel=agg_kernel, lane_chunk=lane_chunk, assign=assign,
        hfel_cfg=hfel_cfg, target_acc=target_acc, n_rounds=n_rounds,
        traced_sched=traced_sched, codec=codec)


@functools.partial(jax.jit, static_argnames=_SCAN_STATICS + ("mesh",))
def sweep_scan_sharded(apply_fn, sp: cm.SystemParams, sp_assign, params_b,
                       u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, X_b, y_b,
                       mask_b, sizes_b, dev_pos_b, edge_pos_b, Xt_b, yt_b,
                       sched_rs, sched_state_b, assign_keys_b, done_b,
                       drl_params, lr, codec_state_b=None,
                       codec_base_b=None, codec_r0=None, *, M: int, L: int,
                       Q: int, alloc_steps: int, mesh,
                       train_only: bool = False,
                       agg_kernel: bool = False,
                       lane_chunk: Optional[int] = None,
                       assign: str = "geo", hfel_cfg=None,
                       target_acc: Optional[float] = None,
                       n_rounds: int = 1, traced_sched=None, codec=None):
    """``sweep_scan`` laid out over a 1-D ``Mesh(("lane",))``.

    Each device runs the ENTIRE R-round scan — traced scheduling,
    assignment search, round body, eval, done-mask — on its S/d lane
    block as one SPMD program: still exactly one dispatch for the whole
    sweep, now lane-parallel. Lanes are independent, so there are no
    collectives; the (R, S, H) schedule tensor and the (R, S) outputs
    shard on their lane axis only (``parallel.sharding.round_lane_spec``).
    S must be a multiple of the device count (``SweepRunner`` pads with
    dead done-masked lanes, exactly as in ``sweep_round_sharded``).
    """
    from repro.parallel.sharding import round_lane_spec
    lane, rep = PartitionSpec("lane"), PartitionSpec()
    rlane = round_lane_spec()
    codec_on = codec is not None and codec.active

    def block(params_b, u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, X_b, y_b,
              mask_b, sizes_b, dev_pos_b, edge_pos_b, Xt_b, yt_b,
              sched_rs, sched_state_b, assign_keys_b, done_b, drl_params,
              lr, *cargs):
        cstate, cbase, cr0 = cargs if codec_on else (None, None, None)
        return _sweep_scan_lanes(
            apply_fn, sp, sp_assign, params_b, u_b, D_b, p_b, g_b,
            g_cloud_b, B_m_b, X_b, y_b, mask_b, sizes_b, dev_pos_b,
            edge_pos_b, Xt_b, yt_b, sched_rs, sched_state_b,
            assign_keys_b, done_b, drl_params, lr, cstate, cbase, cr0,
            M=M, L=L, Q=Q,
            alloc_steps=alloc_steps, train_only=train_only,
            agg_kernel=agg_kernel, lane_chunk=lane_chunk, assign=assign,
            hfel_cfg=hfel_cfg, target_acc=target_acc, n_rounds=n_rounds,
            traced_sched=traced_sched, codec=codec)

    in_specs = (lane,) * 15 + (rlane, lane, lane, lane, rep, rep)
    carry_specs = (lane, lane, lane, lane)
    args = (params_b, u_b, D_b, p_b, g_b, g_cloud_b, B_m_b, X_b,
            y_b, mask_b, sizes_b, dev_pos_b, edge_pos_b, Xt_b,
            yt_b, sched_rs, sched_state_b, assign_keys_b, done_b,
            drl_params, lr)
    if codec_on:
        in_specs = in_specs + (lane, lane, rep)
        carry_specs = carry_specs + (lane, rep)
        args = args + (codec_state_b, codec_base_b, codec_r0)
    sharded = jax.shard_map(
        block, mesh=mesh,
        in_specs=in_specs,
        out_specs=(carry_specs, (rlane, rlane, rlane)),
        check_vma=False)
    return sharded(*args)


def _mod_assign(pop: cm.Population, sched: np.ndarray, rng) -> np.ndarray:
    """Fixed round-robin assignment (Fig. 3/4 training-only sweeps)."""
    return np.asarray(sched) % pop.n_edges


def _geo_assign(pop: cm.Population, sched: np.ndarray, rng) -> np.ndarray:
    """Delegates to the canonical GeoAssigner (sp is unused by it)."""
    return np.asarray(GeoAssigner(None).assign(pop, sched, rng)[0])


ASSIGN_FNS: Dict[str, Callable] = {"mod": _mod_assign, "geo": _geo_assign}


def make_hfel_assign(sp: cm.SystemParams, *, n_transfer: int = 40,
                     n_exchange: int = 80, alloc_steps: int = 100,
                     n_candidates: int = 16) -> Callable:
    """Assignment callable driving the batched K-candidate HFEL search
    (``assign="hfel"`` in ``SweepRunner.run``). Reduced trial budget by
    default: sweeps re-assign every round, so per-round search latency
    matters more than squeezing the last percent of J(Ψ)."""
    from repro.core.assignment.hfel import HFELAssigner
    assigner = HFELAssigner(sp, n_transfer=n_transfer,
                            n_exchange=n_exchange, alloc_steps=alloc_steps,
                            search="batched", n_candidates=n_candidates)

    def fn(pop: cm.Population, sched: np.ndarray, rng) -> np.ndarray:
        return np.asarray(assigner.assign(pop, sched, rng)[0])

    return fn


def make_drl_assign(sp: cm.SystemParams, params) -> Callable:
    """Assignment callable wrapping a trained D3QN agent (greedy) —
    ``assign="drl"`` in ``SweepRunner.run``. ``params`` is the trained
    parameter pytree (``D3QNTrainer.params``); Q evaluation goes through
    the module-level jitted entry shared with the trainer, so all lanes
    reuse one compiled program."""
    from repro.core.assignment.drl import DRLAssigner
    assigner = DRLAssigner(sp, params)

    def fn(pop: cm.Population, sched: np.ndarray, rng) -> np.ndarray:
        return np.asarray(assigner.assign(pop, sched, rng)[0])

    return fn


class SweepRunner:
    """Vmapped multi-lane driver for the fused round engine.

    worlds: list of (Population, FederatedData), one per sweep lane —
    identical shapes required (same N devices, M edges, test-set size).
    Each lane gets its own model init, scheduler state and host RNG; the
    per-round compute of ALL lanes is a single jitted dispatch.

    shard=True lays the lane axis out over a 1-D ``Mesh(("lane",))``
    (``mesh``, default ``launch.mesh.sweep_mesh()`` over all local
    devices) and runs every round through ``sweep_round_sharded``: one
    SPMD program, each device owning an S/d lane block. S is padded up
    to a multiple of the device count with *dead lanes* — clones of lane
    0 that are born with the per-lane done-mask set, so they freeze
    their params, report zero costs and never consume host rng or
    assignment search; all outputs are unpadded back to the real S. The
    shard=False vmapped path is the parity oracle
    (``tests/test_sweep_shard.py``).

    lane_chunk=k executes lanes in sequential vmapped chunks of k (per
    device block when sharded) instead of one whole-axis vmap — a CPU
    cache-blocking knob, see ``_sweep_round_lanes``; leave None on
    accelerators.
    """

    def __init__(self, sp: cm.SystemParams,
                 worlds: Sequence[Tuple[cm.Population, FederatedData]],
                 *, lr: float = 0.01, alloc_steps: int = 100,
                 model_seed: int = 0, agg_kernel: bool = False,
                 shard: bool = False, mesh=None,
                 lane_chunk: Optional[int] = None,
                 compression: Optional[comp.CompressionConfig] = None,
                 arch: str = "hfl-cnn"):
        assert len(worlds) >= 1
        self.sp, self.lr, self.alloc_steps = sp, lr, alloc_steps
        self.arch = arch
        self.spec = get_hfl_spec(arch)
        if self.spec.frozen_init_fn is not None:
            raise ValueError(
                f"SweepRunner trains whole payloads; {self.spec.arch!r} "
                "fine-tunes a frozen base (use HFLFramework)")
        self.agg_kernel = agg_kernel
        self.lane_chunk = lane_chunk
        self.codec = (compression if compression is not None
                      else comp.CompressionConfig())
        self.pops = [w[0] for w in worlds]
        self.feds = [w[1] for w in worlds]
        self.S = len(worlds)
        self.M = self.pops[0].n_edges
        self.N = self.feds[0].n_devices

        if shard:
            from repro.launch.mesh import sweep_mesh
            from repro.parallel.sharding import pad_lanes
            self.mesh = mesh if mesh is not None else sweep_mesh()
            if tuple(self.mesh.axis_names) != ("lane",):
                raise ValueError("shard=True needs a 1-D ('lane',) mesh "
                                 f"(got axes {self.mesh.axis_names})")
            self.S_pad = pad_lanes(self.S, self.mesh.devices.size)
            block = self.S_pad // self.mesh.devices.size
        else:
            self.mesh = None
            self.S_pad = self.S
            block = self.S
        if lane_chunk is not None and block % lane_chunk != 0:
            raise ValueError(
                f"lane_chunk={lane_chunk} must divide the per-device "
                f"lane block ({block})")
        self._n_dead = self.S_pad - self.S

        Dmax = max(int(max(len(y) for y in fed.y)) for fed in self.feds)
        padded = [pad_device_data(fed, Dmax) for fed in self.feds]
        self.X_b = jnp.stack([p[0] for p in padded])      # (S, N, Dmax, ...)
        self.y_b = jnp.stack([p[1] for p in padded])
        self.mask_b = jnp.stack([p[2] for p in padded])
        self.Xt_b = jnp.stack([jnp.asarray(f.X_test) for f in self.feds])
        self.yt_b = jnp.stack([jnp.asarray(f.y_test) for f in self.feds])
        self.fed_sizes_b = jnp.stack(
            [jnp.asarray(f.sizes, jnp.float32) for f in self.feds])
        self.u_b = jnp.stack([p.u for p in self.pops])
        self.D_b = jnp.stack([p.D for p in self.pops])
        self.p_b = jnp.stack([p.p for p in self.pops])
        self.g_b = jnp.stack([p.g for p in self.pops])
        self.g_cloud_b = jnp.stack([p.g_cloud for p in self.pops])
        self.B_m_b = jnp.stack([p.B_m for p in self.pops])
        self.dev_pos_b = jnp.stack(
            [jnp.asarray(p.dev_pos) for p in self.pops])
        self.edge_pos_b = jnp.stack(
            [jnp.asarray(p.edge_pos) for p in self.pops])

        # per-lane model inits from the arch spec (lane worlds share
        # shapes, so feds[0] fixes the payload geometry for all lanes)
        keys = jax.random.split(jax.random.PRNGKey(model_seed), self.S)
        inits = [self.spec.init_fn(k, self.feds[0]) for k in keys]
        self.params0 = jax.tree.map(lambda *xs: jnp.stack(xs), *inits)
        self.apply_fn = self.spec.apply_fn
        self.model_bits = tree_bytes(inits[0]) * 8
        # codec="none" gives exactly model_bits, so the sp the round jits
        # see is value-identical to the uncompressed runner's (same jit
        # cache entry -> bitwise parity).
        self.uplink_bits = comp.message_bits(self.codec, inits[0])

        if self.mesh is not None:
            self._shard_lane_stacks()

    def _codec_state0(self):
        """Fresh lane-stacked error-feedback state: ``(dev_resid
        (S_pad, N, ...), edge_resid (S_pad, M, ...))`` zero trees shaped
        like one lane's params, lane-sharded when the runner is. None for
        the identity codec."""
        if not self.codec.active:
            return None
        p0 = jax.tree.map(lambda x: x[0], self.params0)
        state = (comp.init_state(self.codec, p0, self.N),
                 comp.init_state(self.codec, p0, self.M))
        state = jax.tree.map(
            lambda z: jnp.zeros((self.S_pad,) + z.shape, z.dtype), state)
        if self.mesh is not None:
            from repro.parallel.sharding import lane_sharding
            sh = lane_sharding(self.mesh)
            state = jax.tree.map(lambda z: jax.device_put(z, sh), state)
        return state

    def _codec_base_keys(self, seeds):
        """Per-lane codec key bases ``fold_in(PRNGKey(codec.seed),
        lane_seed)`` — the host loop folds the round index in per round,
        the fused scan folds the carried round counter in in-scan, so
        both engines draw the identical ``compression.round_key``
        stream."""
        lane_seeds = jnp.asarray(
            list(seeds) + [seeds[0]] * self._n_dead, jnp.uint32)
        base = jax.random.PRNGKey(self.codec.seed)
        keys = jax.vmap(lambda s: jax.random.fold_in(base, s))(lane_seeds)
        if self.mesh is not None:
            from repro.parallel.sharding import lane_sharding
            keys = jax.device_put(keys, lane_sharding(self.mesh))
        return keys

    def _shard_lane_stacks(self):
        """Pad every lane-stacked array up to S_pad with clones of lane 0
        (dead lanes: done-masked from round 0, outputs discarded) and lay
        the lane axis out over the mesh so round inputs are born resident
        on their owning devices instead of resharding every dispatch."""
        from repro.parallel.sharding import lane_sharding
        sh = lane_sharding(self.mesh)

        def prep(a):
            if self._n_dead:
                a = jnp.concatenate(
                    [a, jnp.repeat(a[:1], self._n_dead, axis=0)])
            return jax.device_put(a, sh)

        for name in ("X_b", "y_b", "mask_b", "Xt_b", "yt_b", "fed_sizes_b",
                     "u_b", "D_b", "p_b", "g_b", "g_cloud_b", "B_m_b",
                     "dev_pos_b", "edge_pos_b"):
            setattr(self, name, prep(getattr(self, name)))
        self.params0 = jax.tree.map(prep, self.params0)

    # ---------------------------------------------------------------- run

    def run(self, schedulers: Sequence, n_rounds: int,
            assign: Union[str, Callable] = "geo",
            seeds: Optional[Sequence[int]] = None,
            target_acc: Optional[float] = None,
            sizes: str = "pop", train_only: bool = False,
            drl_params=None, fused: Union[bool, str] = False,
            assign_seed: int = 0,
            hfel_opts: Optional[Dict] = None) -> Dict:
        """Run n_rounds of all S lanes; lane s uses schedulers[s].

        assign: "geo" | "mod" | "hfel" (batched K-candidate search via
        ``make_hfel_assign``) | "drl" (greedy trained D3QN agent via
        ``make_drl_assign``; requires ``drl_params``) |
        callable(pop, sched, rng) -> (H,) edges.
        drl_params: trained D3QN parameter pytree
        (``D3QNTrainer.params``), consumed only by ``assign="drl"``.
        sizes: Algorithm-1 aggregation weights — "pop" (cost-model pop.D,
        HFLFramework semantics) or "fed" (actual federated partition
        sizes, the Fig. 3/4 training-curve semantics).
        train_only=True skips resource allocation / cost bookkeeping
        (T_i, E_i are zeros).
        Early stop is per lane: a lane that reaches ``target_acc`` is
        marked done — its model freezes, its assignment search is
        skipped (the lane reuses its last schedule/assignment) and its
        T_i/E_i rows are zero from then on — and the loop breaks once
        every lane is done.

        fused=True runs the whole sweep — scheduling, traced assignment,
        R rounds, eval, done-mask — as ONE jitted dispatch
        (``sweep_scan`` / ``sweep_scan_sharded``); ``fused="oracle"``
        drives the identical traced step in a per-round host loop (one
        dispatch per round) and is the fused path's parity baseline.
        Fused mode needs a *named* assigner (mod/geo/drl/hfel — the
        traced twins run in-scan; callables cannot be traced); hfel
        proposals draw from a JAX key stream seeded by ``assign_seed``
        (host-rng-free), tunable via ``hfel_opts`` (n_transfer,
        n_exchange, n_candidates, warm_steps, accept_top — defaults
        match ``make_hfel_assign``). Schedulers may be the host state
        machines (their (R, S, H) schedules are precomputed up front —
        exact, since scheduling never depends on training state) or
        per-lane ``TracedFedAvg`` instances (drawn in-scan from carried
        PRNG-key state). The result dict gains ``n_dispatches``.

        Returns {"acc": (S, R), "T_i": (S, R), "E_i": (S, R),
        "msg_bits_per_round": float, "iters": (S,) rounds to target_acc
        (or n_rounds), "obj": (S, R)} as numpy arrays.
        """
        assert len(schedulers) == self.S
        if fused not in (False, True, "oracle"):
            raise ValueError(f"fused must be False, True or 'oracle', "
                             f"got {fused!r}")
        if fused:
            return self._run_fused(
                schedulers, n_rounds, assign=assign, seeds=seeds,
                target_acc=target_acc, sizes=sizes, train_only=train_only,
                drl_params=drl_params, oracle=(fused == "oracle"),
                assign_seed=assign_seed, hfel_opts=hfel_opts)
        if isinstance(assign, str):
            if assign == "hfel":
                assign_fn = make_hfel_assign(self.sp,
                                             alloc_steps=self.alloc_steps)
            elif assign == "drl":
                if drl_params is None:
                    raise ValueError(
                        "assign='drl' needs drl_params (a trained "
                        "D3QNTrainer.params pytree)")
                assign_fn = make_drl_assign(self.sp, drl_params)
            else:
                assign_fn = ASSIGN_FNS[assign]
        else:
            assign_fn = assign
        if sizes not in ("pop", "fed"):
            raise ValueError(f"sizes must be 'pop' or 'fed', got {sizes!r}")
        sizes_b = self.D_b if sizes == "pop" else self.fed_sizes_b
        if seeds is None:
            seeds = list(range(self.S))
        rngs = [np.random.default_rng(s) for s in seeds]
        sp = dataclasses.replace(self.sp,
                                 model_bits=float(self.uplink_bits))
        codec_on = self.codec.active
        cstate = self._codec_state0()
        cbase = self._codec_base_keys(seeds) if codec_on else None

        params_b = self.params0
        accs: List[np.ndarray] = []
        Ts: List[np.ndarray] = []
        Es: List[np.ndarray] = []
        H = None
        # dead pad lanes (sharding only) are done from round 0: frozen
        # params, zero costs, no host rng / search spend, outputs sliced
        # away below.
        done = np.zeros(self.S_pad, bool)
        done[self.S:] = True
        scheds = [None] * self.S
        assigns = [None] * self.S
        for r_i in range(n_rounds):
            # done lanes are frozen: reuse their last schedule/assignment
            # instead of spending scheduler rng and assignment search on
            # a lane that no longer trains.
            scheds = [scheds[s] if done[s]
                      else np.asarray(schedulers[s].schedule(rngs[s]))
                      for s in range(self.S)]
            # IKC/VKC lanes can come up short of the nominal cohort when a
            # lane's clustering left clusters empty (K' < K); top the short
            # lanes up from their unscheduled pool (Alg. 3/4 lines 12-15)
            # so every lane shares one (S, H) shape.
            H = max(len(s) for s in scheds)
            # route through the scheduler's topup_to so rotation-state
            # policies (IKC) record the extra picks in G_k; plain _topup
            # covers caller-supplied scheduler objects without one.
            scheds = [np.asarray(
                          schedulers[i].topup_to(s, H, rngs[i])
                          if hasattr(schedulers[i], "topup_to")
                          else _topup(list(s), self.N, H, rngs[i]))
                      if len(s) < H else s
                      for i, s in enumerate(scheds)]
            assigns = [assigns[s] if done[s]
                       else np.asarray(assign_fn(self.pops[s], scheds[s],
                                                 rngs[s]))
                       for s in range(self.S)]
            # dead pad lanes alias lane 0's cohort (no rng consumed; their
            # round output is masked by done and discarded).
            pad = [scheds[0]] * self._n_dead
            sched_b = jnp.asarray(np.stack(scheds + pad))
            assign_b = jnp.asarray(np.stack(
                assigns + [assigns[0]] * self._n_dead))
            ckw = {}
            if codec_on:
                ckw = dict(codec=self.codec, codec_state_b=cstate,
                           codec_keys_b=jax.vmap(
                               lambda k: jax.random.fold_in(k, r_i))(cbase))
            if self.mesh is not None:
                out = sweep_round_sharded(
                    self.apply_fn, sp, params_b, self.u_b, self.D_b,
                    self.p_b, self.g_b, self.g_cloud_b, self.B_m_b,
                    self.X_b, self.y_b, self.mask_b, sizes_b, sched_b,
                    assign_b, self.lr, M=self.M, L=sp.L, Q=sp.Q,
                    alloc_steps=self.alloc_steps, mesh=self.mesh,
                    train_only=train_only, agg_kernel=self.agg_kernel,
                    lane_chunk=self.lane_chunk, done_b=jnp.asarray(done),
                    **ckw)
            else:
                out = sweep_round(
                    self.apply_fn, sp, params_b, self.u_b, self.D_b,
                    self.p_b, self.g_b, self.g_cloud_b, self.B_m_b,
                    self.X_b, self.y_b, self.mask_b, sizes_b, sched_b,
                    assign_b, self.lr, M=self.M, L=sp.L, Q=sp.Q,
                    alloc_steps=self.alloc_steps, train_only=train_only,
                    agg_kernel=self.agg_kernel, lane_chunk=self.lane_chunk,
                    done_b=jnp.asarray(done), **ckw)
            if codec_on:
                params_b, (T_i, E_i), cstate = out
            else:
                params_b, (T_i, E_i) = out
            acc_full = self._eval(params_b)              # (S_pad,)
            acc = acc_full[:self.S]
            accs.append(acc)
            Ts.append(np.asarray(T_i)[:self.S])
            Es.append(np.asarray(E_i)[:self.S])
            if target_acc is not None:
                done = done | (acc_full >= target_acc)
                if done.all():
                    break

        acc_a = np.stack(accs, axis=1)                  # (S, R)
        T_a = np.stack(Ts, axis=1)
        E_a = np.stack(Es, axis=1)
        R = acc_a.shape[1]
        if target_acc is not None:
            reached = acc_a >= target_acc
            iters = np.where(reached.any(axis=1),
                             reached.argmax(axis=1) + 1, R)
        else:
            iters = np.full(self.S, R)
        msg_bits = cm.round_msg_bits(self.sp, sp.Q * H, self.M,
                                     msg_bits=self.uplink_bits)
        return {"acc": acc_a, "T_i": T_a, "E_i": E_a,
                "obj": E_a + sp.lam * T_a, "iters": iters,
                "msg_bits_per_round": float(msg_bits), "H": H,
                "codec": self.codec.codec,
                "uplink_bits_per_msg": float(self.uplink_bits),
                "uplink_bytes_per_round": float(msg_bits / 8)}

    # --------------------------------------------------------- fused run

    def _run_fused(self, schedulers: Sequence, n_rounds: int, *,
                   assign, seeds, target_acc, sizes, train_only,
                   drl_params, oracle: bool, assign_seed: int,
                   hfel_opts) -> Dict:
        """``run(fused=...)`` body: one ``sweep_scan`` dispatch for the
        whole sweep (oracle=False) or a per-round host loop over the
        identical traced step (oracle=True, the parity baseline)."""
        if not isinstance(assign, str):
            raise ValueError(
                "fused sweeps need a named assigner (mod/geo/drl/hfel) — "
                "callables cannot run inside the scan")
        if assign not in ("mod", "geo", "drl", "hfel"):
            raise ValueError(f"unknown assign {assign!r} for fused run")
        if assign == "drl" and drl_params is None:
            raise ValueError("assign='drl' needs drl_params (a trained "
                             "D3QNTrainer.params pytree)")
        if sizes not in ("pop", "fed"):
            raise ValueError(f"sizes must be 'pop' or 'fed', got {sizes!r}")
        if hfel_opts and assign != "hfel":
            raise ValueError("hfel_opts only applies to assign='hfel'")
        hfel_cfg = None
        if assign == "hfel":
            opts = dict(hfel_opts or {})
            bad = set(opts) - set(_HFEL_FUSED_DEFAULTS)
            if bad:
                raise ValueError(
                    f"unknown hfel_opts keys {sorted(bad)}; valid: "
                    f"{sorted(_HFEL_FUSED_DEFAULTS)} (alloc_steps is the "
                    "runner's constructor knob)")
            hfel_cfg = tuple(sorted({**_HFEL_FUSED_DEFAULTS, **opts}.items()))
        sizes_b = self.D_b if sizes == "pop" else self.fed_sizes_b
        if seeds is None:
            seeds = list(range(self.S))
        sp = dataclasses.replace(self.sp,
                                 model_bits=float(self.uplink_bits))
        codec_on = self.codec.active
        cstate = self._codec_state0()
        cbase = self._codec_base_keys(seeds) if codec_on else None
        cr = jnp.int32(0) if codec_on else None

        # -- scheduling: in-scan TracedFedAvg state, or an exact host
        #    precompute (scheduling never reads training state, so the
        #    (R, S, H) tensor reproduces the host loop's draws verbatim).
        n_traced = sum(isinstance(s, TracedFedAvg) for s in schedulers)
        if n_traced == self.S:
            traced_sched = schedulers[0]
            if any(s != traced_sched for s in schedulers):
                raise ValueError(
                    "fused TracedFedAvg lanes must share one (n_devices, "
                    "H) config — per-lane variation lives in the seed")
            H = traced_sched.H
            states = [traced_sched.init_state(seeds[s])
                      for s in range(self.S)]
            states += [states[0]] * self._n_dead
            sched_state_b = jnp.stack(states)
            sched_rs = None
        elif n_traced:
            raise ValueError("cannot mix TracedFedAvg and host schedulers "
                             "in one fused run")
        else:
            traced_sched = None
            sched_state_b = None
            rngs = [np.random.default_rng(s) for s in seeds]
            rounds = []
            H = None
            for _ in range(n_rounds):
                # identical rng-consumption order to the host loop: all
                # lanes' schedule draws, then all lanes' topups.
                scheds = [np.asarray(schedulers[s].schedule(rngs[s]))
                          for s in range(self.S)]
                H_r = max(len(s) for s in scheds)
                scheds = [np.asarray(
                              schedulers[i].topup_to(s, H_r, rngs[i])
                              if hasattr(schedulers[i], "topup_to")
                              else _topup(list(s), self.N, H_r, rngs[i]))
                          if len(s) < H_r else s
                          for i, s in enumerate(scheds)]
                if H is None:
                    H = H_r
                elif H_r != H:
                    raise ValueError(
                        f"fused sweeps need a round-constant cohort size "
                        f"(got H={H} then H={H_r}); use the per-round host "
                        "path for schedulers whose worst-case cohort "
                        "varies across rounds")
                rounds.append(np.stack(scheds + [scheds[0]] * self._n_dead))
            sched_rs = jnp.asarray(np.stack(rounds))     # (R, S_pad, H)

        base = jax.random.PRNGKey(assign_seed)
        lane_seeds = jnp.asarray(
            list(seeds) + [seeds[0]] * self._n_dead, jnp.uint32)
        assign_keys_b = jax.vmap(
            lambda s: jax.random.fold_in(base, s))(lane_seeds)
        done0 = np.zeros(self.S_pad, bool)
        done0[self.S:] = True
        done_b = jnp.asarray(done0)
        params_b = self.params0
        statics = dict(M=self.M, L=sp.L, Q=sp.Q, alloc_steps=self.alloc_steps,
                       train_only=train_only, agg_kernel=self.agg_kernel,
                       lane_chunk=self.lane_chunk, assign=assign,
                       hfel_cfg=hfel_cfg, target_acc=target_acc,
                       traced_sched=traced_sched,
                       codec=self.codec if codec_on else None)
        if self.mesh is not None:
            fn = functools.partial(sweep_scan_sharded, mesh=self.mesh)
        else:
            fn = sweep_scan

        def dispatch(params_b, done_b, sched_state_b, assign_keys_b,
                     sched_rs, n_r, codec_state_b=None, codec_r0=None):
            return fn(self.apply_fn, sp, self.sp, params_b, self.u_b,
                      self.D_b, self.p_b, self.g_b, self.g_cloud_b,
                      self.B_m_b, self.X_b, self.y_b, self.mask_b, sizes_b,
                      self.dev_pos_b, self.edge_pos_b, self.Xt_b, self.yt_b,
                      sched_rs, sched_state_b, assign_keys_b, done_b,
                      drl_params if assign == "drl" else None, self.lr,
                      codec_state_b, cbase, codec_r0,
                      n_rounds=n_r, **statics)

        if oracle:
            # per-round host loop over the SAME traced step: the fused
            # path's dispatch-per-round parity baseline.
            accs, Ts, Es = [], [], []
            n_dispatches = 0
            for r in range(n_rounds):
                xs_r = None if sched_rs is None else sched_rs[r:r + 1]
                carry, (acc_r, T_r, E_r) = dispatch(
                    params_b, done_b, sched_state_b, assign_keys_b, xs_r, 1,
                    cstate, cr)
                if codec_on:
                    (params_b, done_b, sched_state_b, assign_keys_b,
                     cstate, cr) = carry
                else:
                    params_b, done_b, sched_state_b, assign_keys_b = carry
                n_dispatches += 1
                accs.append(np.asarray(acc_r)[0, :self.S])
                Ts.append(np.asarray(T_r)[0, :self.S])
                Es.append(np.asarray(E_r)[0, :self.S])
                if target_acc is not None and np.asarray(done_b).all():
                    break
            acc_a = np.stack(accs, axis=1)               # (S, R_run)
            T_a = np.stack(Ts, axis=1)
            E_a = np.stack(Es, axis=1)
        else:
            _, (acc_rs, T_rs, E_rs) = dispatch(
                params_b, done_b, sched_state_b, assign_keys_b, sched_rs,
                n_rounds, cstate, cr)
            n_dispatches = 1
            acc_a = np.asarray(acc_rs)[:, :self.S].T     # (S, R)
            T_a = np.asarray(T_rs)[:, :self.S].T
            E_a = np.asarray(E_rs)[:, :self.S].T
            if target_acc is not None:
                # trim trailing all-done rounds so the fused result is
                # row-for-row comparable with the early-breaking host loop
                # (done lanes' extra rows are frozen-acc / zero-cost).
                reached_by = np.maximum.accumulate(
                    acc_a >= target_acc, axis=1)
                all_done = reached_by.all(axis=0)
                if all_done.any():
                    R_eff = int(all_done.argmax()) + 1
                    acc_a = acc_a[:, :R_eff]
                    T_a = T_a[:, :R_eff]
                    E_a = E_a[:, :R_eff]

        R = acc_a.shape[1]
        if target_acc is not None:
            reached = acc_a >= target_acc
            iters = np.where(reached.any(axis=1),
                             reached.argmax(axis=1) + 1, R)
        else:
            iters = np.full(self.S, R)
        msg_bits = cm.round_msg_bits(self.sp, sp.Q * H, self.M,
                                     msg_bits=self.uplink_bits)
        return {"acc": acc_a, "T_i": T_a, "E_i": E_a,
                "obj": E_a + sp.lam * T_a, "iters": iters,
                "msg_bits_per_round": float(msg_bits), "H": H,
                "codec": self.codec.codec,
                "uplink_bits_per_msg": float(self.uplink_bits),
                "uplink_bytes_per_round": float(msg_bits / 8),
                "n_dispatches": n_dispatches}

    def _eval(self, params_b, batch: int = 512) -> np.ndarray:
        n = self.Xt_b.shape[1]
        accs, ns = [], []
        for i in range(0, n, batch):
            a = _sweep_eval(self.apply_fn, params_b,
                            self.Xt_b[:, i:i + batch],
                            self.yt_b[:, i:i + batch])
            accs.append(np.asarray(a))
            ns.append(min(batch, n - i))
        return np.average(np.stack(accs, axis=0), axis=0, weights=ns)

    # ---------------------------------------------------- ratio sweeps

    def sweep_ratios(self, ratios: Sequence[float], *, scheduler: str,
                     n_rounds: int, assign: Union[str, Callable] = "geo",
                     K: int = 10, seeds: Optional[Sequence[int]] = None,
                     target_acc: Optional[float] = None) -> Dict:
        """Paper-style scheduling-ratio sweep: H = ratio * N for each
        ratio in ``ratios`` (e.g. 0.3 / 0.5 / 1.0), each ratio one
        vmapped multi-lane run. Returns {ratio: run-result}."""
        if seeds is None:
            seeds = list(range(self.S))
        out = {}
        for r in ratios:
            H = max(1, int(round(r * self.N)))
            name = "fedavg" if H >= self.N else scheduler
            scheds = [build_scheduler(name, self.feds[s], self.sp, H, K=K,
                                      lr=self.lr, seed=seeds[s],
                                      arch=self.arch)
                      for s in range(self.S)]
            out[r] = self.run(scheds, n_rounds, assign=assign, seeds=seeds,
                              target_acc=target_acc)
        return out
