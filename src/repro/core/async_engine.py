"""Event-driven asynchronous HFL engine — arrivals, dropouts, stragglers.

Every engine up to PR 7 is synchronous-round: the whole scheduled cohort
trains in lockstep and the round "takes" ``max`` of the member latencies.
Real IoT fleets are intermittent — devices join mid-round, drop out with
work in flight, and stragglers inflate the critical path. This module
runs one HFL global iteration as a *discrete-event simulation* on a
virtual clock:

* Scheduling, assignment and the convex resource allocation (27) are
  identical to the fused round engine — ``_alloc_and_price`` reuses the
  exact ``allocate_batch``/``select_device_allocation`` pattern of
  ``framework.round_step_core`` and prices each device's task with the
  per-device eq. (4)-(8) time/energy instead of the per-round reduction.
* Each dispatched device runs its L local GD steps (Algorithm 1 inner
  loop) and *returns the update at a trace-determined virtual time*:
  ``(t_cmp + t_com) * latency_scale`` (straggler inflation, optional
  log-normal jitter), driven by an :class:`~repro.core.cost_model.
  AvailabilityTrace` of arrival/dropout flips.
* Edge servers aggregate from FedBuff-style staleness-weighted buffers:
  a delivered update that trained against edge version ``v`` is merged
  at version ``V`` with weight ``D_n / (1 + (V - v))**a`` (eq. (2)
  generalised); the data mass of cohort members with nothing in the
  buffer anchors on the current edge model. After Q buffer flushes the
  edge uploads to the cloud; the cloud aggregates with the eq.-(3)
  cohort-data-size weights.
* Device state (dispatched / delivered / aborted) rides one
  fixed-shape ``(H, ...)`` cohort pytree. A dispatch trains only the
  lanes its boolean mask sets, compacted into fixed chunks of
  ``DISPATCH_CHUNK`` lanes, so every dispatch pattern reuses the same
  compiled program.

Parity: with the degenerate trace (``AvailabilityTrace.always_on``,
unit latency scale, no jitter, wait-for-all buffers) the event loop
reproduces the synchronous ``round_step`` — same b/f allocations and
per-task costs bitwise, totals to float-accumulation-order tolerance,
same model params to ulp — pinned in ``tests/test_async_engine.py``
and documented as the oracle recipe in ``docs/async.md``.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import compression as comp
from repro.core import cost_model as cm
from repro.core import resource as ra
from repro.configs.registry import get_hfl_spec
from repro.core.hfl import agg_matmul, evaluate_in_batches, pad_device_data
from repro.core.local_train import cohort_local_sgd
from repro.data.partition import FederatedData
from repro.utils import tree_bytes


# ------------------------------------------------------ jitted helpers

@functools.partial(jax.jit, static_argnames=("sp", "M", "alloc_steps"))
def _alloc_and_price(sp, u, D, p, g, g_cloud, B_m, assign, *, M: int,
                     alloc_steps: int):
    """Cohort allocation + per-task pricing, one dispatch.

    The same all-edges ``allocate_batch`` / ``select_device_allocation``
    pattern as ``framework.round_step_core``, but returning the
    *per-device* task time/energy ``tc``/``ec`` (H,) so the event loop
    can spend them task by task, plus the per-edge cloud-hop costs.
    """
    H = assign.shape[0]
    edge_mask = assign[None, :] == jnp.arange(M)[:, None]       # (M, H)
    res = ra.allocate_batch(
        sp,
        jnp.broadcast_to(u, (M, H)), jnp.broadcast_to(D, (M, H)),
        jnp.broadcast_to(p, (M, H)), g.T, B_m, edge_mask,
        steps=alloc_steps)
    b, f = ra.select_device_allocation(res, assign)             # (H,) each
    g_sel = g[jnp.arange(H), assign]
    tc = cm.t_cmp(sp, u, D, f) + cm.t_com(sp, b, g_sel, p)
    ec = cm.e_cmp(sp, u, D, f) + cm.e_com(sp, b, g_sel, p)
    T_cl, E_cl = cm.cloud_cost(sp, g_cloud)                     # (M,) each
    return b, f, tc, ec, T_cl, E_cl


# Lanes per chunk of a dispatch's training loop: a dispatch of k lanes
# trains ceil(k / C) * C lanes, C = min(DISPATCH_CHUNK, H). On a TPU v5e
# a lane costs about the same at 4, 8 or 16 lanes a chunk, so the
# narrowest of those wastes the fewest padding lanes (PERF.md).
DISPATCH_CHUNK = 4


def dispatch_chunk(H: int) -> int:
    """Lanes per chunk of a dispatch from an H-lane cohort."""
    return min(DISPATCH_CHUNK, H)


def _train_in_chunks(apply_fn, edge_params, assign, dispatch_mask, X, y,
                     mask, lr, L, carry, keep):
    """Train the dispatched lanes, C at a time, into ``(H, ...)`` rows.

    The dispatched lanes are compacted in lane order and padded with the
    out-of-range index H to whole chunks; ``ceil(k / C)`` chunks run in
    one ``fori_loop``, so one compiled program serves every dispatch
    pattern. Chunk ``i`` pulls its lanes' edge models, trains them with
    ``cohort_local_sgd`` and scatters ``keep(i, lanes, carry, pulled,
    trained)`` (a pytree shaped like ``carry`` with C rows) back into
    ``carry``. Padding lanes train a copy of the chunk's first lane and
    are dropped by the scatter; lanes not dispatched come out bitwise
    unchanged.
    """
    H = dispatch_mask.shape[0]
    C = dispatch_chunk(H)
    idx = jnp.nonzero(dispatch_mask, size=-(-H // C) * C, fill_value=H)[0]
    n = (jnp.sum(dispatch_mask, dtype=jnp.int32) + C - 1) // C

    def chunk(i, carry):
        part = jax.lax.dynamic_slice(idx, (i * C,), (C,))
        lanes = jnp.where(part < H, part, part[0])
        pulled = jax.tree.map(lambda e: jnp.take(e, assign[lanes], axis=0),
                              edge_params)
        trained = cohort_local_sgd(apply_fn, pulled, X[lanes], y[lanes],
                                   mask[lanes], L, lr)
        rows = keep(i, lanes, carry, pulled, trained)
        return jax.tree.map(lambda c, r: c.at[part].set(r, mode="drop"),
                            carry, rows)

    return jax.lax.fori_loop(0, n, chunk, carry)


@functools.partial(jax.jit, static_argnames=("apply_fn", "L"))
def _train_dispatched(apply_fn, cohort_params, edge_params, assign,
                      dispatch_mask, X, y, mask, lr, *, L: int):
    """Pull edge models and run L local GD steps on the dispatched lanes.

    Only the lanes where ``dispatch_mask`` is set train, from their
    edge's current model, in fixed chunks (``_train_in_chunks``); the
    other lanes of ``cohort_params`` are returned as they came.
    """
    return _train_in_chunks(
        apply_fn, edge_params, assign, dispatch_mask, X, y, mask, lr, L,
        cohort_params, lambda i, lanes, carry, pulled, trained: trained)


@functools.partial(jax.jit, static_argnames=("apply_fn", "L", "codec"))
def _train_dispatched_compressed(apply_fn, cohort_params, edge_params,
                                 assign, dispatch_mask, X, y, mask, lr,
                                 resid, key, *, L: int, codec):
    """``_train_dispatched`` with the uplink codec applied.

    Dispatched lanes train from their edge model, then ship
    ``encode(trained - pulled + resid)``; the buffered value is the
    edge-side reconstruction ``pulled + decode(...)`` (the staleness-
    weighted flush is linear in the decoded update, so merging the
    reconstruction is exactly merging the wire-format update).
    ``resid``: (H, ...) error-feedback rows for the scheduled cohort —
    updated only on dispatched lanes, like the params. Chunk ``i``
    encodes with ``fold_in(key, i)``.
    """
    def keep(i, lanes, carry, pulled, trained):
        delta = jax.tree.map(lambda t, q: (t - q).astype(jnp.float32),
                             trained, pulled)
        dec, new_resid = comp.encode_decode(
            codec, jax.random.fold_in(key, i), delta,
            jax.tree.map(lambda r: r[lanes], carry[1]))
        recon = jax.tree.map(lambda q, d: (q + d).astype(q.dtype),
                             pulled, dec)
        return recon, new_resid

    return _train_in_chunks(apply_fn, edge_params, assign, dispatch_mask, X,
                            y, mask, lr, L, (cohort_params, resid), keep)


@jax.jit
def _flush_edge(edge_params, cohort_params, m, deliver_mask, member_mask,
                sizes, staleness, a):
    """Staleness-weighted buffer flush for edge ``m`` (eq. (2) general).

    Delivered members contribute with weight ``D_n / (1+staleness_n)**a``;
    the data mass of cohort members with nothing in the buffer anchors on
    the current edge model, so a flush with a partial buffer moves the
    edge model proportionally to the fresh data it actually received.
    With all members delivered at staleness 0 this reduces bitwise to the
    synchronous eq.-(2) weights (the parity-oracle path). An edge whose
    weight mass is zero keeps its model (the ``has_dev`` fixup).
    """
    w_dev = sizes.astype(jnp.float32)
    decay = (1.0 + staleness) ** a
    w_del = jnp.where(deliver_mask, w_dev / decay, 0.0)
    w_anchor = jnp.sum(jnp.where(member_mask & ~deliver_mask, w_dev, 0.0))
    tot = jnp.sum(w_del) + w_anchor
    denom = jnp.maximum(tot, 1.0)
    wn = w_del / denom
    wa = w_anchor / denom

    def agg(e, c):
        flat = c.reshape(c.shape[0], -1)
        new = agg_matmul(wn, flat) + wa * e[m].reshape(-1)
        new = jnp.where(tot > 0, new, e[m].reshape(-1))
        return e.at[m].set(new.reshape(e.shape[1:]).astype(e.dtype))

    return jax.tree.map(agg, edge_params, cohort_params)


@functools.partial(jax.jit, static_argnames=("M",))
def _cloud_agg(edge_params, assign, sizes, *, M: int):
    """Eq. (3): cloud aggregation with cohort-data-size weights —
    identical op order to ``hfl_global_iteration_core``'s cloud path."""
    onehot = jax.nn.one_hot(assign, M, dtype=jnp.float32)
    w_dev = sizes.astype(jnp.float32)
    edge_tot = agg_matmul(onehot.T, w_dev)
    w = jnp.where(edge_tot > 0, edge_tot, 0.0)
    w = w / jnp.maximum(jnp.sum(w), 1.0)

    def agg(e):
        flat = e.reshape(M, -1)
        return agg_matmul(w, flat).reshape(e.shape[1:]).astype(e.dtype)

    return jax.tree.map(agg, edge_params)


@functools.partial(jax.jit, static_argnames=("M", "codec"))
def _cloud_agg_compressed(edge_params, global_params, assign, sizes, resid,
                          key, *, M: int, codec):
    """Compressed eq.-(3): each edge ships ``encode(edge - global)``, the
    cloud aggregates the decoded deltas in delta space (identical weights
    to ``_cloud_agg`` — exact when the codec is lossless). Returns
    ``(new_global, new_edge_resid)``."""
    onehot = jax.nn.one_hot(assign, M, dtype=jnp.float32)
    edge_tot = agg_matmul(onehot.T, sizes.astype(jnp.float32))
    w = jnp.where(edge_tot > 0, edge_tot, 0.0)
    w = w / jnp.maximum(jnp.sum(w), 1.0)

    delta = jax.tree.map(
        lambda e, g_: (e - g_[None]).astype(jnp.float32),
        edge_params, global_params)
    dec, new_resid = comp.encode_decode(codec, key, delta, resid)

    def agg(g_, d):
        flat = d.reshape(M, -1)
        return (g_.reshape(-1) + agg_matmul(w, flat)).reshape(
            g_.shape).astype(g_.dtype)

    return jax.tree.map(agg, global_params, dec), new_resid


# ----------------------------------------------------------- the engine

@dataclasses.dataclass
class AsyncConfig:
    """Event-loop knobs. The defaults are the sync-parity setting:
    wait-for-all buffers, no jitter (pair with ``always_on`` traces)."""
    H: int = 20                     # scheduled cohort size
    arch: str = "hfl-cnn"           # model payload (configs.registry id)
    scheduler: str = "fedavg"       # fedavg | ikc | vkc
    K: int = 10                     # clusters (ikc/vkc)
    staleness_exp: float = 0.5      # a in D_n/(1+staleness)^a
    buffer_size: Optional[int] = None   # edge flush threshold; None =
                                        # wait for every in-flight member
    lr: float = 0.01
    alloc_steps: int = 100
    seed: int = 0
    jitter_sigma: float = 0.0       # per-task log-normal latency noise
    max_events_per_round: int = 100_000   # liveness guard
    compression: comp.CompressionConfig = dataclasses.field(
        default_factory=comp.CompressionConfig)


class AsyncHFLEngine:
    """Virtual-clock asynchronous HFL over an availability trace.

    ``step_round()`` runs ONE cloud round as a discrete-event loop:
    dispatch the scheduled cohort, deliver updates at trace-determined
    times, flush staleness-weighted edge buffers Q times per edge, then
    cloud-aggregate and advance the virtual clock by the round makespan.
    The model/scheduler setup mirrors ``HFLFramework`` (same
    ``cfg.arch``-resolved :class:`~repro.models.spec.ModelSpec`, same
    key derivation for the model init, same ``model_bits`` patching) so
    sync and async runs start from identical states for any payload.
    """

    def __init__(self, sp: cm.SystemParams, pop: cm.Population,
                 fed: FederatedData, cfg: AsyncConfig,
                 trace: Optional[cm.AvailabilityTrace] = None,
                 scheduler=None, assigner=None):
        self.pop, self.cfg, self.fed = pop, cfg, fed
        key = jax.random.PRNGKey(cfg.seed)
        k_model, _, _ = jax.random.split(key, 3)
        self.spec = get_hfl_spec(cfg.arch)
        if self.spec.frozen_init_fn is not None:
            raise ValueError(
                f"AsyncHFLEngine trains whole payloads; {self.spec.arch!r} "
                "fine-tunes a frozen base (use HFLFramework)")
        self.model_params = self.spec.init_fn(k_model, fed)
        self.apply_fn = self.spec.apply_fn
        self.sp = dataclasses.replace(
            sp, model_bits=float(tree_bytes(self.model_params) * 8))
        self.codec = cfg.compression
        self.uplink_bits = comp.message_bits(self.codec, self.model_params)
        # allocation + pricing see the codec's actual bits-per-message;
        # codec="none" gives exactly model_bits, so sp_round equals
        # self.sp (same frozen dataclass -> same jit cache entry ->
        # bitwise sync parity).
        self.sp_round = dataclasses.replace(
            self.sp, model_bits=float(self.uplink_bits))
        self.dev_resid = comp.init_state(self.codec, self.model_params,
                                         fed.n_devices)
        self.edge_resid = comp.init_state(self.codec, self.model_params,
                                          pop.n_edges)
        self.X, self.y, self.mask = pad_device_data(fed)

        if scheduler is None:
            from repro.core.sweep import build_scheduler
            scheduler = build_scheduler(cfg.scheduler, fed, self.sp, cfg.H,
                                        K=cfg.K, lr=cfg.lr, seed=cfg.seed,
                                        arch=cfg.arch)
        self.scheduler = scheduler
        if assigner is None:
            from repro.core.assignment import GeoAssigner
            assigner = GeoAssigner(self.sp)
        self.assigner = assigner

        self.trace = trace or cm.AvailabilityTrace.always_on(pop.n_devices)
        assert self.trace.n_devices == pop.n_devices, \
            "availability trace / population size mismatch"
        self.rng = np.random.default_rng(cfg.seed)
        self.t = 0.0                    # virtual clock [s]
        self.round = 0
        self.history: List[Dict] = []
        self.last_sched: Optional[np.ndarray] = None
        self.last_assign: Optional[np.ndarray] = None
        self.last_alloc = None          # (b, f, tc, ec) of the last round

    # ------------------------------------------------------------ round

    def step_round(self, collect_eval: bool = True) -> Dict:
        """One cloud round. Host spans ``async.schedule``,
        ``async.assign``, ``async.price``, ``async.dispatch`` (each
        ``_train_dispatched`` call), ``async.flush`` (each
        ``_flush_edge`` call), ``async.cloud_agg`` and ``async.eval``,
        each with ``round=`` the record's round, go into whatever
        ``jax.profiler`` trace is running; the record counts
        ``n_dispatches``, ``lanes_dispatched`` and ``lanes_trained``
        (whole chunks of ``dispatch_chunk(H)`` lanes)."""
        sp, pop, cfg = self.sp, self.pop, self.cfg
        M, Q = pop.n_edges, sp.Q
        t0 = self.t
        rnd = self.round + 1

        with TraceAnnotation("async.schedule", round=rnd):
            sched = np.asarray(self.scheduler.schedule(self.rng))
        with TraceAnnotation("async.assign", round=rnd):
            assign_np, _ = self.assigner.assign(pop, sched, self.rng)
            assign_np = np.asarray(assign_np)
        self.last_sched, self.last_assign = sched, assign_np
        H = len(sched)
        assign_j = jnp.asarray(assign_np, jnp.int32)
        sizes = pop.D[sched]

        with TraceAnnotation("async.price", round=rnd):
            b, f, tc, ec, T_cl, E_cl = _alloc_and_price(
                self.sp_round, pop.u[sched], pop.D[sched], pop.p[sched],
                pop.g[sched], pop.g_cloud, pop.B_m, assign_j, M=M,
                alloc_steps=cfg.alloc_steps)
            self.last_alloc = (b, f, tc, ec)
            ec_h = np.asarray(ec, np.float64)
            T_cl_h = np.asarray(T_cl, np.float64)
            lat = (np.asarray(tc, np.float64)
                   * self.trace.latency_scale[sched])

        codec_on = self.codec.active
        cohort_resid = None
        if codec_on:
            cohort_resid = jax.tree.map(lambda r_: r_[sched],
                                        self.dev_resid)
            k_disp, k_cloud = jax.random.split(
                comp.round_key(self.codec, cfg.seed, self.round))

        Xc, yc, mc = self.X[sched], self.y[sched], self.mask[sched]
        edge_params = jax.tree.map(
            lambda g_: jnp.broadcast_to(g_[None], (M,) + g_.shape),
            self.model_params)
        cohort_params = jax.tree.map(
            lambda g_: jnp.broadcast_to(g_[None], (H,) + g_.shape),
            self.model_params)

        # --- per-slot event-loop state (cohort-indexed)
        up = self.trace.up_at(t0)[sched].copy()      # (H,) availability
        delivered = np.zeros(H, bool)                # in an edge buffer
        task_id = np.full(H, -1, np.int64)           # -1 = idle/aborted
        start_ver = np.zeros(H, np.int64)            # edge ver at dispatch
        edge_ver = np.zeros(M, np.int64)
        flushes = np.zeros(M, np.int64)
        edge_finish = np.full(M, t0, np.float64)
        edge_energy = np.zeros(M, np.float64)        # aggregated-task J
        members = [np.flatnonzero(assign_np == m) for m in range(M)]
        for m in range(M):                           # empty edges: done,
            if len(members[m]) == 0:                 # cloud hop only
                flushes[m] = Q
        stats = {"n_agg": 0, "n_stale": 0, "max_stale": 0,
                 "n_aborted": 0, "wasted_j": 0.0,
                 "n_dispatches": 0, "lanes_dispatched": 0,
                 "lanes_trained": 0}
        chunk = dispatch_chunk(H)

        heap: list = []
        seq = 0
        next_task = 0
        tog_rows = [self.trace.toggles[d] for d in sched]
        tog_ptr = [int(np.searchsorted(row, t0, side="right"))
                   for row in tog_rows]

        def push(t, kind, payload):
            nonlocal seq
            heapq.heappush(heap, (t, seq, kind, payload))
            seq += 1

        for s in range(H):
            i = tog_ptr[s]
            if i < len(tog_rows[s]) and np.isfinite(tog_rows[s][i]):
                push(float(tog_rows[s][i]), "toggle", s)

        def dispatch(slots, t):
            nonlocal cohort_params, cohort_resid, next_task
            slots = [s for s in slots
                     if up[s] and not delivered[s] and task_id[s] < 0
                     and flushes[assign_np[s]] < Q]
            if not slots:
                return
            dmask = np.zeros(H, bool)
            dmask[slots] = True
            with TraceAnnotation("async.dispatch", round=rnd):
                if codec_on:
                    cohort_params, cohort_resid = \
                        _train_dispatched_compressed(
                            self.apply_fn, cohort_params, edge_params,
                            assign_j, jnp.asarray(dmask), Xc, yc, mc,
                            cfg.lr, cohort_resid,
                            jax.random.fold_in(k_disp,
                                               stats["n_dispatches"]),
                            L=sp.L, codec=self.codec)
                else:
                    cohort_params = _train_dispatched(
                        self.apply_fn, cohort_params, edge_params,
                        assign_j, jnp.asarray(dmask), Xc, yc, mc, cfg.lr,
                        L=sp.L)
            stats["n_dispatches"] += 1
            stats["lanes_dispatched"] += len(slots)
            stats["lanes_trained"] += -(-len(slots) // chunk) * chunk
            for s in slots:
                start_ver[s] = edge_ver[assign_np[s]]
                task_id[s] = next_task
                next_task += 1
                mult = 1.0
                if cfg.jitter_sigma > 0:
                    mult = float(np.exp(
                        self.rng.normal(0.0, cfg.jitter_sigma)))
                push(t + lat[s] * mult, "done", (s, task_id[s]))

        def do_flush(m, t, redispatch=True):
            nonlocal edge_params
            mem = members[m]
            del_mask = np.zeros(H, bool)
            del_mask[mem] = delivered[mem]
            mem_mask = np.zeros(H, bool)
            mem_mask[mem] = True
            stal = np.where(del_mask, edge_ver[m] - start_ver, 0)
            with TraceAnnotation("async.flush", round=rnd):
                edge_params = _flush_edge(
                    edge_params, cohort_params, jnp.int32(m),
                    jnp.asarray(del_mask), jnp.asarray(mem_mask),
                    sizes, jnp.asarray(stal, jnp.float32),
                    jnp.float32(cfg.staleness_exp))
            d_slots = np.flatnonzero(del_mask)
            edge_energy[m] += float(ec_h[d_slots].sum())
            stats["n_agg"] += len(d_slots)
            if len(d_slots):
                s_max = int(stal[d_slots].max())
                stats["max_stale"] = max(stats["max_stale"], s_max)
                stats["n_stale"] += int((stal[d_slots] > 0).sum())
            delivered[d_slots] = False
            edge_ver[m] += 1
            flushes[m] += 1
            if flushes[m] >= Q:
                edge_finish[m] = t
            elif redispatch:
                dispatch(list(d_slots), t)

        def should_flush(m):
            if flushes[m] >= Q:
                return False
            mem = members[m]
            n_del = int(delivered[mem].sum())
            in_flight = int((task_id[mem] >= 0).sum())
            if n_del > 0 and in_flight == 0:
                return True          # buffer drained — nothing to wait on
            return (cfg.buffer_size is not None
                    and n_del >= min(cfg.buffer_size, len(mem)))

        # --- run the round
        dispatch(list(np.flatnonzero(up)), t0)
        events = 0
        while not np.all(flushes >= Q):
            if not heap or events >= cfg.max_events_per_round:
                break                # liveness guard: forced drain below
            t, _, kind, payload = heapq.heappop(heap)
            events += 1
            self.t = max(self.t, t)
            if kind == "toggle":
                s = payload
                tog_ptr[s] += 1
                i = tog_ptr[s]
                if i < len(tog_rows[s]) and np.isfinite(tog_rows[s][i]):
                    push(float(tog_rows[s][i]), "toggle", s)
                up[s] = not up[s]
                m = int(assign_np[s])
                if up[s]:
                    dispatch([s], t)         # mid-round arrival
                else:
                    if task_id[s] >= 0:      # dropout aborts in-flight
                        task_id[s] = -1
                        stats["wasted_j"] += float(ec_h[s])
                        stats["n_aborted"] += 1
                    if should_flush(m):
                        do_flush(m, t)
            else:                            # task completion
                s, tid = payload
                if tid != task_id[s]:
                    continue                 # aborted / superseded task
                task_id[s] = -1
                m = int(assign_np[s])
                if flushes[m] >= Q:          # edge already uploaded
                    stats["wasted_j"] += float(ec_h[s])
                    stats["n_aborted"] += 1
                    continue
                delivered[s] = True
                if should_flush(m):
                    do_flush(m, t)

        forced = int(np.maximum(Q - flushes, 0).sum())
        for m in range(M):                   # forced drain (liveness)
            while flushes[m] < Q:
                do_flush(m, self.t, redispatch=False)
        heap.clear()

        # --- round totals + eq.-(3) cloud aggregation
        T_m = (edge_finish - t0) + T_cl_h
        T_round = float(T_m.max()) if M else 0.0
        E_round = float(edge_energy.sum() + np.asarray(E_cl).sum())
        with TraceAnnotation("async.cloud_agg", round=rnd):
            if codec_on:
                self.model_params, self.edge_resid = _cloud_agg_compressed(
                    edge_params, self.model_params, assign_j, sizes,
                    self.edge_resid, k_cloud, M=M, codec=self.codec)
                self.dev_resid = jax.tree.map(
                    lambda full, r_: full.at[jnp.asarray(sched)].set(r_),
                    self.dev_resid, cohort_resid)
            else:
                self.model_params = _cloud_agg(edge_params, assign_j, sizes,
                                               M=M)
        self.t = t0 + T_round
        self.round += 1

        acc = None
        if collect_eval:
            with TraceAnnotation("async.eval", round=rnd):
                acc = evaluate_in_batches(self.apply_fn, self.model_params,
                                          self.fed.X_test, self.fed.y_test)
        rec = {"round": self.round, "t": self.t, "acc": acc,
               "T_i": T_round, "E_i": E_round,
               "obj_i": E_round + sp.lam * T_round,
               "H": H, "n_updates": stats["n_agg"],
               "n_stale": stats["n_stale"],
               "max_staleness": stats["max_stale"],
               "n_aborted": stats["n_aborted"],
               "wasted_j": stats["wasted_j"],
               "forced_flushes": forced,
               "n_dispatches": stats["n_dispatches"],
               "lanes_dispatched": stats["lanes_dispatched"],
               "lanes_trained": stats["lanes_trained"],
               "msg_bits": cm.round_msg_bits(self.sp, stats["n_agg"], M,
                                             msg_bits=self.uplink_bits),
               "uplink_bytes": float(
                   (stats["n_agg"] + M) * self.uplink_bits / 8),
               "codec": self.codec.codec}
        self.history.append(rec)
        return rec

    # ------------------------------------------------------ conveniences

    def run(self, n_rounds: int, target_acc: Optional[float] = None,
            eval_every: int = 1, verbose: bool = False) -> Dict:
        for r in range(1, n_rounds + 1):
            rec = self.step_round(
                collect_eval=eval_every > 0 and r % eval_every == 0)
            if verbose:
                acc = "-" if rec["acc"] is None else f"{rec['acc']:.3f}"
                print(f"  [async] round {rec['round']:3d} t={rec['t']:9.1f}s"
                      f" acc={acc} updates={rec['n_updates']}"
                      f" stale={rec['n_stale']} wasted={rec['wasted_j']:.1f}J")
            if (target_acc is not None and rec["acc"] is not None
                    and rec["acc"] >= target_acc):
                break
        return self.summary()

    def summary(self) -> Dict:
        evals = [r for r in self.history if r["acc"] is not None]
        T = sum(r["T_i"] for r in self.history)
        E = sum(r["E_i"] for r in self.history)
        return {"rounds": len(self.history), "t_virtual": self.t,
                "final_acc": evals[-1]["acc"] if evals else None,
                "T": T, "E": E, "objective": E + self.sp.lam * T,
                "n_updates": sum(r["n_updates"] for r in self.history),
                "n_stale": sum(r["n_stale"] for r in self.history),
                "n_aborted": sum(r["n_aborted"] for r in self.history),
                "wasted_j": sum(r["wasted_j"] for r in self.history),
                "history": self.history}
