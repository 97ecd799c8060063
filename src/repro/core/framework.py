"""Algorithm 6 — the full proposed HFL framework.

Per global iteration i:
  1. schedule H devices (IKC / VKC / FedAvg),
  2. assign them to edges (D3QN / HFEL / geographic),
  3. per-edge convex resource allocation (bandwidth + CPU frequency),
  4. HFL training (Algorithm 1) on the scheduled cohort,
  5. evaluate; stop when the target accuracy is reached.

Steps 3+4 plus the cost bookkeeping (13)/(14) run through the fused
``round_step`` engine: assignment one-hot construction, the vmapped
all-edges resource allocation, ``round_cost`` and the Algorithm-1
training are one jitted program, so a round costs ONE device dispatch +
host sync instead of ~M+3 (the old per-edge Python loop is kept as
``engine="sequential"`` — the parity oracle for tests).
``FrameworkConfig(agg_kernel=True)`` additionally routes the Algorithm-1
edge/cloud aggregation through the fused masked-weight
``kernels/hier_agg`` Pallas kernel (interpret mode off-TPU).

Tracks the paper's reported quantities: accuracy trajectory, T (13),
E (14), objective E + λT (15), and transmitted message volume per round
and cumulative (Fig. 7f/7g), plus the one-off clustering cost (Table II).

The trained payload is pluggable: ``FrameworkConfig.arch`` resolves a
:class:`repro.models.spec.ModelSpec` through ``configs.registry`` —
the default ``"hfl-cnn"`` is the paper's CNN (bitwise-identical to the
pre-spec engines), any other registry id trains that arch's smoke-config
variant as a sequence classifier (see ``docs/engine.md``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import TYPE_CHECKING, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import compression as comp
from repro.core import cost_model as cm
from repro.core import resource as ra
from repro.core.clustering import adjusted_rand_index
from repro.core.hfl import (hfl_global_iteration, hfl_global_iteration_core,
                            pad_device_data)
from repro.core.scheduling import (FedAvgScheduler, IKCScheduler,
                                   VKCScheduler, run_device_clustering)
from repro.core.scheduling.device_clustering import clustering_cost
from repro.configs.registry import get_hfl_spec
from repro.data.partition import FederatedData
from repro.utils import tree_bytes

if TYPE_CHECKING:
    from repro.models.spec import ModelSpec


def round_step_core(apply_fn, sp: cm.SystemParams, params, u, D, p, g,
                    g_cloud, B_m, X, y, mask, sizes, assign, lr, *,
                    M: int, L: int, Q: int, alloc_steps: int,
                    agg_kernel: bool = False, codec=None,
                    codec_state=None, codec_key=None, frozen=None,
                    lane_chunk: int = 0):
    """Traceable fused round: one global iteration minus scheduling.

    Inputs are pre-gathered for the scheduled cohort: u/D/p/sizes (H,),
    g (H, M) gains to every edge, X/y/mask (H, Dmax, ...), assign (H,).
    Fuses (a) per-edge one-hot/mask construction, (b) the vmapped
    all-edges resource allocation (27), (c) round costs (13)/(14) and
    (d) Algorithm-1 training into one program. ``agg_kernel=True`` runs
    the hierarchical aggregation (2)-(3) through the fused masked-weight
    ``kernels/hier_agg`` Pallas kernel (interpret off-TPU) instead of
    masked XLA einsums. Returns (new_params, (T_i, E_i, T_m, E_m, b, f)).
    ``params`` is the trainable tree, ``frozen`` the payload's base (or
    None), held once and read by local training alone, which trains
    ``lane_chunk`` lanes at a time (``cohort_local_sgd``; 0: all).

    Compression: with an active ``codec`` (static
    ``CompressionConfig``), pass the caller's ``sp`` already patched
    with the codec's per-message bits (``compression.message_bits``) so
    the allocation and eqs. (7)-(12) price the compressed payload;
    ``codec_state`` is ``(dev_resid, edge_resid)`` error-feedback trees
    for the cohort (H, ...) and the edges (M, ...). The return then
    becomes ``(new_params, (new_dev_resid, new_edge_resid), aux)``.
    """
    H = assign.shape[0]
    # the plan and its price, problem (27); training and aggregation
    # carry the scopes local_train / aggregate (hfl.py, local_train.py)
    with jax.named_scope("allocate"):
        edge_mask = assign[None, :] == jnp.arange(M)[:, None]   # (M, H)
        res = ra.allocate_batch(
            sp,
            jnp.broadcast_to(u, (M, H)), jnp.broadcast_to(D, (M, H)),
            jnp.broadcast_to(p, (M, H)), g.T, B_m, edge_mask,
            steps=alloc_steps)
        b, f = ra.select_device_allocation(res, assign)         # (H,) each
        g_sel = g[jnp.arange(H), assign]
        T_i, E_i, T_m, E_m = cm.round_cost_gathered(
            sp, u, D, p, g_sel, g_cloud, assign, b, f, M)
    if codec is not None and codec.active:
        dev_resid, edge_resid = codec_state
        new_params, dev_resid, edge_resid = hfl_global_iteration_core(
            apply_fn, params, X, y, mask, sizes, assign, M=M, L=L, Q=Q,
            lr=lr, agg_kernel=agg_kernel, codec=codec,
            dev_resid=dev_resid, edge_resid=edge_resid,
            codec_key=codec_key, frozen=frozen, lane_chunk=lane_chunk)
        return new_params, (dev_resid, edge_resid), (T_i, E_i, T_m, E_m,
                                                     b, f)
    new_params = hfl_global_iteration_core(
        apply_fn, params, X, y, mask, sizes, assign, M=M, L=L, Q=Q, lr=lr,
        agg_kernel=agg_kernel, frozen=frozen, lane_chunk=lane_chunk)
    return new_params, (T_i, E_i, T_m, E_m, b, f)


@functools.partial(jax.jit, static_argnames=(
    "apply_fn", "sp", "M", "L", "Q", "alloc_steps", "agg_kernel", "codec",
    "lane_chunk"))
def round_step(apply_fn, sp: cm.SystemParams, params, u, D, p, g, g_cloud,
               B_m, X, y, mask, sizes, assign, lr, *, M: int, L: int,
               Q: int, alloc_steps: int, agg_kernel: bool = False,
               codec=None, codec_state=None, codec_key=None, frozen=None,
               lane_chunk: int = 0):
    """Jitted fused round — see ``round_step_core``."""
    return round_step_core(apply_fn, sp, params, u, D, p, g, g_cloud, B_m,
                           X, y, mask, sizes, assign, lr,
                           M=M, L=L, Q=Q, alloc_steps=alloc_steps,
                           agg_kernel=agg_kernel, codec=codec,
                           codec_state=codec_state, codec_key=codec_key,
                           frozen=frozen, lane_chunk=lane_chunk)


@dataclasses.dataclass
class FrameworkConfig:
    arch: str = "hfl-cnn"           # model payload (configs.registry id)
    scheduler: str = "ikc"          # ikc | vkc | fedavg
    assigner: str = "geo"           # drl | hfel | geo
    H: int = 50
    K: int = 10
    lr: float = 0.01
    target_acc: float = 0.875
    max_iters: int = 100
    alloc_steps: int = 200
    seed: int = 0
    use_kernel: bool = False        # Pallas kmeans kernel (interpret on CPU)
    agg_kernel: bool = False        # Pallas hier_agg aggregation backend
    engine: str = "fused"           # fused | sequential (per-edge oracle)
    hfel_search: str = "batched"    # batched | serial (assigner="hfel")
    hfel_candidates: int = 16       # K moves per batched HFEL round
    compression: comp.CompressionConfig = dataclasses.field(
        default_factory=comp.CompressionConfig)   # uplink update codec


class HFLFramework:
    def __init__(self, sp: cm.SystemParams, pop: cm.Population,
                 fed: FederatedData, cfg: FrameworkConfig,
                 drl_params: Optional[dict] = None,
                 spec: Optional[ModelSpec] = None):
        self.sp, self.pop, self.fed, self.cfg = sp, pop, fed, cfg
        self.rng = np.random.default_rng(cfg.seed)
        key = jax.random.PRNGKey(cfg.seed)
        k_model, k_mini, k_cluster = jax.random.split(key, 3)

        # payload resolution: cfg.arch -> ModelSpec, unless the caller
        # hands one in. The default "hfl-cnn" reproduces the paper CNN
        # construction bit for bit (same key-split order, same cnn_apply
        # object -> same jit cache entries as the pre-spec engines).
        self.spec = spec if spec is not None else get_hfl_spec(cfg.arch)
        self.model_params = self.spec.init_fn(k_model, fed)
        self.apply_fn = self.spec.apply_fn
        # a payload with a frozen base: held here once; model_params is
        # then the trainable tree alone (what a device uploads)
        self.frozen = None
        if self.spec.frozen_init_fn is not None:
            if cfg.scheduler == "vkc":
                raise ValueError("vkc clusters with the whole model; a "
                                 "payload with a frozen base needs ikc or "
                                 "fedavg")
            self.frozen = self.spec.frozen_init_fn(
                jax.random.fold_in(k_model, 1), fed)
        self.model_bits = tree_bytes(self.model_params) * 8
        self.sp = dataclasses.replace(self.sp, model_bits=float(self.model_bits))

        # uplink codec: compressed per-message bits price every uplink
        # (device->edge and edge->cloud ship the same codec), so the
        # round sp the allocator/cost model see carries them; identity
        # codec => uplink_bits == model_bits and sp_round == sp (the
        # same jit cache entry — bitwise parity with the seed path).
        self.codec = cfg.compression
        if self.codec.active and cfg.engine == "sequential":
            raise ValueError("compression requires engine='fused' (the "
                             "sequential oracle ships raw payloads)")
        self.uplink_bits = comp.message_bits(self.codec, self.model_params)
        self.sp_round = dataclasses.replace(
            self.sp, model_bits=float(self.uplink_bits))
        self.codec_state = None
        if self.codec.active:
            self.codec_state = (
                comp.init_state(self.codec, self.model_params,
                                fed.n_devices),
                comp.init_state(self.codec, self.model_params,
                                pop.n_edges))

        self.X, self.y, self.mask = pad_device_data(fed)
        # (N,) valid samples per device, for the pad share
        self._n_valid = np.asarray(self.mask).sum(axis=1).astype(np.int64)
        self.clustering_stats: Dict = {}
        self._setup_scheduler(k_mini, k_cluster)
        self._setup_assigner(drl_params)
        self.history: List[Dict] = []
        self.last_sched: Optional[np.ndarray] = None
        self.last_assign: Optional[np.ndarray] = None

    # ------------------------------------------------------------ setup

    def _setup_scheduler(self, k_mini, k_cluster):
        # mirrored by core/sweep.py build_scheduler (standalone, different
        # key derivation, no cost/ARI bookkeeping) — keep the clustering
        # recipe in sync with it
        cfg, fed = self.cfg, self.fed
        h = max(1, cfg.H // cfg.K)
        if cfg.scheduler == "fedavg":
            self.scheduler = FedAvgScheduler(fed.n_devices, cfg.H)
            return
        if cfg.scheduler == "ikc":
            # auxiliary mini model ξ on the spec's clustering crop
            # (images: 1x10x10 random crops; sequences: token crops)
            mini_params = self.spec.mini_init_fn(k_mini, fed)
            compute_scale = (tree_bytes(mini_params)
                             / max(1, tree_bytes(self.model_params)))
            crop = self.spec.mini_preprocess_fn(self.X, k_mini)
            aux_bits = tree_bytes(mini_params) * 8
            labels, _ = run_device_clustering(
                k_cluster, self.spec.mini_apply_fn, mini_params, crop,
                self.y, self.mask, cfg.K, self.sp.L, cfg.lr,
                use_kernel=cfg.use_kernel)
            self.scheduler = IKCScheduler(labels, h)
        else:  # vkc: heavyweight global model as auxiliary model
            aux_bits = self.model_bits
            labels, _ = run_device_clustering(
                k_cluster, self.apply_fn, self.model_params, self.X, self.y,
                self.mask, cfg.K, self.sp.L, cfg.lr,
                use_kernel=cfg.use_kernel)
            self.scheduler = VKCScheduler(labels, h)
            compute_scale = 1.0
        delay, energy = clustering_cost(self.sp, self.pop, aux_bits,
                                        compute_scale=compute_scale)
        self.clustering_stats = {
            "ari": adjusted_rand_index(labels, self.fed.majority_class),
            "delay_s": delay, "energy_j": energy,
            "aux_bits": float(aux_bits)}

    def _setup_assigner(self, drl_params):
        from repro.core.assignment import (DRLAssigner, GeoAssigner,
                                           HFELAssigner)
        a = self.cfg.assigner
        if a == "drl":
            assert drl_params is not None, "need trained D3QN params"
            self.assigner = DRLAssigner(self.sp, drl_params)
        elif a == "hfel":
            self.assigner = HFELAssigner(
                self.sp, search=self.cfg.hfel_search,
                n_candidates=self.cfg.hfel_candidates)
        else:
            self.assigner = GeoAssigner(self.sp)

    # ------------------------------------------------------------- round

    def run_round(self, i: int) -> Dict:
        """One global iteration. Host spans ``hfl.schedule``,
        ``hfl.assign``, ``hfl.cohort``, ``hfl.round_step``, ``hfl.eval``
        and ``hfl.record`` (each with ``round=i``) go into whatever
        ``jax.profiler`` trace is running; the record counts
        ``assign_latency_s`` and ``pad_share``."""
        sp, pop = self.sp, self.pop
        with TraceAnnotation("hfl.schedule", round=i):
            sched = np.asarray(self.scheduler.schedule(self.rng))
        with TraceAnnotation("hfl.assign", round=i):
            t0 = time.perf_counter()
            assign, _ = self.assigner.assign(pop, sched, self.rng)
            assign = np.asarray(assign)
            assign_latency = time.perf_counter() - t0
        self.last_sched, self.last_assign = sched, assign
        H = len(sched)

        if self.cfg.engine == "sequential":
            with TraceAnnotation("hfl.round_step", round=i):
                T_i, E_i = self._sequential_alloc_cost_train(sched, assign)
        else:
            # gathers of the cohort's rows and the assignment's upload
            with TraceAnnotation("hfl.cohort", round=i):
                D = pop.D[sched]
                cohort = (pop.u[sched], D, pop.p[sched], pop.g[sched],
                          pop.g_cloud, pop.B_m, self.X[sched],
                          self.y[sched], self.mask[sched], D,
                          jnp.asarray(assign))
                if self.codec.active:
                    dev_resid, edge_resid = self.codec_state
                    cohort_resid = jax.tree.map(lambda r: r[sched],
                                                dev_resid)
            # the round program's dispatch; it runs on while the host
            # goes on to the evaluation, whose first chunk waits for it
            with TraceAnnotation("hfl.round_step", round=i):
                if self.codec.active:
                    (self.model_params, (cohort_resid, edge_resid),
                     (T_i, E_i, _, _, _, _)) = round_step(
                        self.apply_fn, self.sp_round, self.model_params,
                        *cohort, self.cfg.lr,
                        M=pop.n_edges, L=sp.L, Q=sp.Q,
                        alloc_steps=self.cfg.alloc_steps,
                        agg_kernel=self.cfg.agg_kernel, codec=self.codec,
                        codec_state=(cohort_resid, edge_resid),
                        codec_key=comp.round_key(self.codec, self.cfg.seed,
                                                 i), frozen=self.frozen,
                        lane_chunk=self.spec.lane_chunk)
                    self.codec_state = (
                        jax.tree.map(lambda full, nr: full.at[sched].set(nr),
                                     dev_resid, cohort_resid),
                        edge_resid)
                else:
                    self.model_params, (T_i, E_i, _, _, _, _) = round_step(
                        self.apply_fn, sp, self.model_params,
                        *cohort, self.cfg.lr,
                        M=pop.n_edges, L=sp.L, Q=sp.Q,
                        alloc_steps=self.cfg.alloc_steps,
                        agg_kernel=self.cfg.agg_kernel, frozen=self.frozen,
                        lane_chunk=self.spec.lane_chunk)

        with TraceAnnotation("hfl.eval", round=i):
            acc = self.spec.eval_fn(self.model_params, self.fed.X_test,
                                    self.fed.y_test, frozen=self.frozen)
        with TraceAnnotation("hfl.record", round=i):
            msg_bits = cm.round_msg_bits(self.sp, sp.Q * H, pop.n_edges,
                                         msg_bits=self.uplink_bits)
            rec = {"iter": i, "acc": acc, "T_i": float(T_i),
                   "E_i": float(E_i),
                   "obj_i": float(E_i + sp.lam * T_i),
                   "msg_bits": float(msg_bits),
                   "uplink_bytes": float(sp.Q * H * self.uplink_bits / 8),
                   "codec": self.codec.codec,
                   "assign_latency_s": assign_latency,
                   # share of the cohort's (H, Dmax) sample slots that are
                   # padding: trained on, masked out of the loss
                   "pad_share": 1.0 - int(self._n_valid[sched].sum())
                                 / (H * self.mask.shape[1]),
                   "H": H,
                   # what each device sends (the trainable tree, coded)
                   # and the base held once on the device
                   "uplink_bits": float(self.uplink_bits),
                   "frozen_bytes": float(tree_bytes(self.frozen))}
            if jnp.issubdtype(self.X.dtype, jnp.integer):
                # tokens the round's local training runs over (L Q steps)
                steps = sp.L * sp.Q * int(np.prod(self.X.shape[2:]))
                rec["tokens_valid"] = steps * int(self._n_valid[sched].sum())
                rec["tokens_padded"] = steps * H * self.mask.shape[1]
        self.history.append(rec)
        return rec

    def _sequential_alloc_cost_train(self, sched, assign):
        """Pre-engine per-edge path: M separate allocate dispatches with
        host round-trips, then round_cost + Algorithm 1. Kept verbatim as
        the parity oracle for the fused engine."""
        sp, pop = self.sp, self.pop
        H = len(sched)
        b = np.zeros(H)
        f = np.zeros(H)
        for m in range(pop.n_edges):
            mask = jnp.asarray(assign == m)
            res = ra.allocate(sp, pop.u[sched], pop.D[sched], pop.p[sched],
                              pop.g[sched, m], pop.B_m[m], mask,
                              steps=self.cfg.alloc_steps)
            sel = np.asarray(assign == m)
            b[sel] = np.asarray(res.b)[sel]
            f[sel] = np.asarray(res.f)[sel]

        T_i, E_i, _, _ = cm.round_cost(
            sp, pop, jnp.asarray(sched), jnp.asarray(assign),
            jnp.asarray(b), jnp.asarray(f))

        # Algorithm 1
        self.model_params = hfl_global_iteration(
            self.apply_fn, self.model_params,
            self.X[sched], self.y[sched], self.mask[sched],
            self.pop.D[sched], jnp.asarray(assign),
            M=pop.n_edges, L=sp.L, Q=sp.Q, lr=self.cfg.lr, frozen=self.frozen,
            lane_chunk=self.spec.lane_chunk)
        return T_i, E_i

    def run(self, verbose: bool = True) -> Dict:
        for i in range(1, self.cfg.max_iters + 1):
            rec = self.run_round(i)
            if verbose:
                print(f"  [{self.cfg.scheduler}/{self.cfg.assigner}] "
                      f"iter {i:3d} acc={rec['acc']:.3f} "
                      f"T_i={rec['T_i']:.1f}s E_i={rec['E_i']:.1f}J")
            if rec["acc"] >= self.cfg.target_acc:
                break
        return self.summary()

    def summary(self) -> Dict:
        T = sum(r["T_i"] for r in self.history)
        E = sum(r["E_i"] for r in self.history)
        return {
            "iters": len(self.history),
            "final_acc": self.history[-1]["acc"] if self.history else 0.0,
            "T": T, "E": E, "objective": E + self.sp.lam * T,
            "total_msg_bits": sum(r["msg_bits"] for r in self.history),
            "msg_bits_per_round": (self.history[-1]["msg_bits"]
                                   if self.history else 0.0),
            "clustering": self.clustering_stats,
            "history": self.history,
        }
