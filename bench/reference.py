"""The plain reference: the paper's semantics in straightforward jax.numpy.

Imports nothing of the system under test. Written from the paper
(arXiv:2402.02506): the CNN of Section VI, eq. (1) full-batch local
gradient descent, eqs. (2)-(3) data-size-weighted edge and cloud
aggregation (Algorithm 1), the staleness-weighted edge buffer of the
streaming engine (eq. (2) with each delivered update weighted by
D_n / (1 + staleness)^a and the data of the members with nothing in the
buffer anchored on the edge model), the round cost eqs. (4)-(14) in
float64 numpy, the per-edge resource allocation of problem (27) solved
afresh in float64 numpy, and the D3QN agent's greedy assignment (the
BiLSTM and dueling heads of Section IV over the eq. (24) features) in
float64 numpy.

Training runs at ``jax.default_matmul_precision("highest")`` in float32
(``dtype=jnp.bfloat16`` gives the control: the same arithmetic one
precision down). Convolutions are ``lax.conv_general_dilated`` and
pooling ``lax.reduce_window``, not the program's im2col and reshape.
Devices are trained in blocks of ``block`` so that the reference fits
the chip beside nothing else.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def precision(dtype):
    """The matmul precision the reference runs at for ``dtype``."""
    if dtype == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


# ----------------------------------------------------------------- model

def cnn_init(key, image_hw, channels, conv1, conv2, kernel, hidden,
             n_classes):
    """He-normal weights, no biases: conv-pool, conv-pool, fc, fc."""
    H, W = image_hw
    h = ((H - kernel + 1) // 2 - kernel + 1) // 2
    w = ((W - kernel + 1) // 2 - kernel + 1) // 2
    flat = h * w * conv2
    shapes = {"conv1": (kernel, kernel, channels, conv1),
              "conv2": (kernel, kernel, conv1, conv2),
              "fc1": (flat, hidden), "fc2": (hidden, n_classes)}
    keys = jax.random.split(key, len(shapes))
    return {name: jax.random.normal(k, s, jnp.float32)
            * jnp.sqrt(2.0 / np.prod(s[:-1]))
            for k, (name, s) in zip(keys, shapes.items())}


def _conv(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "VALID",
                                    dimension_numbers=("NHWC", "HWIO",
                                                       "NHWC"))


def _pool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def logits(params, x):
    x = _pool(jax.nn.relu(_conv(x, params["conv1"])))
    x = _pool(jax.nn.relu(_conv(x, params["conv2"])))
    x = jax.nn.relu(x.reshape(x.shape[0], -1) @ params["fc1"])
    return x @ params["fc2"]


def loss(params, x, y, mask):
    """Mean cross-entropy over the valid samples."""
    logp = jax.nn.log_softmax(logits(params, x).astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _local_gd(params, x, y, mask, L, lr):
    """Eq. (1): L full-batch gradient steps on one device."""
    for _ in range(L):
        g = jax.grad(loss)(params, x, y, mask)
        params = jax.tree.map(lambda p, d: (p - lr * d).astype(p.dtype),
                              params, g)
    return params


def _train_rows(params_rows, X, y, mask, L, lr, block):
    """Local GD for every row, ``block`` rows at a time."""
    return lax.map(lambda a: _local_gd(*a, L, lr),
                   (params_rows, X, y, mask), batch_size=block)


def _weighted(w, rows):
    """sum_i w[..., i] rows[i] for every leaf: (K, n) x (n, ...)."""
    return jax.tree.map(
        lambda r: jnp.tensordot(w.astype(r.dtype), r, axes=1), rows)


# ------------------------------------------------ synchronous round (Alg. 1)

@functools.partial(jax.jit, static_argnames=("M", "L", "Q", "lr", "block"))
def hfl_round(params, X, y, mask, sizes, assign, *, M, L, Q, lr, block):
    """One global iteration of Algorithm 1 for the scheduled cohort.

    X/y/mask (H, Dmax, ...), sizes (H,) the D_n that weigh aggregation,
    assign (H,) edge ids. Edges with no device keep their model and weigh
    nothing in the cloud average. Works in the dtype of ``params``."""
    dt = jax.tree.leaves(params)[0].dtype
    X = X.astype(dt)
    onehot = (assign[:, None] == jnp.arange(M)[None, :]).astype(jnp.float32)
    w = sizes.astype(jnp.float32)
    edge_tot = jnp.sum(onehot * w[:, None], axis=0)                 # (M,)
    w_edge = (onehot * w[:, None]).T / jnp.maximum(edge_tot, 1.0)[:, None]
    has = edge_tot > 0
    edge = jax.tree.map(lambda g: jnp.broadcast_to(g, (M,) + g.shape),
                        params)
    for _ in range(Q):
        dev = jax.tree.map(lambda e: e[assign], edge)
        dev = _train_rows(dev, X, y, mask, L, lr, block)
        new = _weighted(w_edge, dev)
        edge = jax.tree.map(
            lambda n, o: jnp.where(has.reshape((M,) + (1,) * (o.ndim - 1)),
                                   n, o), new, edge)
    w_cloud = jnp.where(has, edge_tot, 0.0)
    w_cloud = w_cloud / jnp.maximum(jnp.sum(w_cloud), 1.0)
    return _weighted(w_cloud, edge)


# ------------------------------------------- streaming round (event replay)

@functools.partial(jax.jit, static_argnames=("L", "lr"))
def train_lanes(edge, assign_rows, X, y, mask, *, L, lr):
    """Lanes pull their edge's model and run eq. (1)."""
    start = jax.tree.map(lambda e: e[assign_rows], edge)
    return jax.vmap(lambda p, a, b, c: _local_gd(p, a, b, c, L, lr))(
        start, X.astype(jax.tree.leaves(edge)[0].dtype), y, mask)


@jax.jit
def flush(edge, cohort, m, delivered, members, sizes, staleness, a):
    """Staleness-weighted buffer flush of edge ``m``: delivered members
    weigh D_n / (1 + s_n)^a, the data of members with nothing delivered
    anchors on the edge model; an edge with no weight keeps its model."""
    w = sizes.astype(jnp.float32)
    w_del = jnp.where(delivered, w / (1.0 + staleness) ** a, 0.0)
    w_anchor = jnp.sum(jnp.where(members & ~delivered, w, 0.0))
    tot = jnp.sum(w_del) + w_anchor
    denom = jnp.maximum(tot, 1.0)

    def one(e, c):
        merged = (jnp.tensordot((w_del / denom).astype(c.dtype), c, axes=1)
                  + (w_anchor / denom).astype(e.dtype) * e[m])
        return e.at[m].set(jnp.where(tot > 0, merged, e[m]))

    return jax.tree.map(one, edge, cohort)


@functools.partial(jax.jit, static_argnames=("M",))
def cloud(edge, sizes, assign, *, M):
    """Eq. (3): edges weigh the data of their cohort members."""
    onehot = (assign[:, None] == jnp.arange(M)[None, :]).astype(jnp.float32)
    edge_tot = jnp.sum(onehot * sizes.astype(jnp.float32)[:, None], axis=0)
    w = jnp.where(edge_tot > 0, edge_tot, 0.0)
    return _weighted(w / jnp.maximum(jnp.sum(w), 1.0), edge)


def stream_round(params, X, y, mask, sizes, assign, events, *, M, L, lr,
                 block):
    """Replay one streaming round: ``events`` is the engine's sequence of
    ("dispatch", lanes mask) and ("flush", m, delivered, members,
    staleness, a). A dispatched lane trains from its edge's model as it
    stands at that moment, ``block`` lanes per call. Returns (global
    params, edge params before the cloud step)."""
    H = len(assign)
    edge = jax.tree.map(lambda g: jnp.broadcast_to(g, (M,) + g.shape),
                        params)
    cohort = jax.tree.map(lambda g: jnp.broadcast_to(g, (H,) + g.shape),
                          params)
    sizes_j = jnp.asarray(sizes, jnp.float32)
    for ev in events:
        if ev[0] == "dispatch":
            lanes = np.flatnonzero(ev[1])
            for i in range(0, len(lanes), block):
                part = lanes[i:i + block]
                rows = np.concatenate(
                    [part, np.full(block - len(part), part[0])])
                trained = train_lanes(edge, jnp.asarray(assign[rows]),
                                      X[rows], y[rows], mask[rows],
                                      L=L, lr=lr)
                idx = jnp.asarray(part)
                cohort = jax.tree.map(
                    lambda c, t, n=len(part): c.at[idx].set(t[:n]),
                    cohort, trained)
        else:
            _, m, delivered, members, staleness, a = ev
            edge = flush(edge, cohort, jnp.int32(m), jnp.asarray(delivered),
                         jnp.asarray(members), sizes_j,
                         jnp.asarray(staleness, jnp.float32),
                         jnp.float32(a))
    return cloud(edge, sizes_j, jnp.asarray(assign), M=M), edge


# ----------------------------------------------------------------- readings

@jax.jit
def test_loss(params, X, y):
    """Mean cross-entropy of ``params`` on the test set, in float32."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    return loss(params, X, y, jnp.ones(y.shape, jnp.float32))


def loss_gap(got, ref, X_test, y_test):
    """Worst relative gap between the test-set losses of two sequences
    of parameters, round by round."""
    Xt, yt = jnp.asarray(X_test), jnp.asarray(y_test)
    with precision(jnp.float32):
        return max(abs(float(test_loss(g, Xt, yt))
                       - float(test_loss(r, Xt, yt)))
                   / float(test_loss(r, Xt, yt)) for g, r in zip(got, ref))


def leaf_norms(tree):
    """{leaf name: float64 norm} of a dict of arrays."""
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def norm_gap(program_delta, reference_delta):
    """Worst leaf's gap between the two norms of a parameter change,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger. Leaves that the reference moves by less than a
    thousandth of the median leaf move by round-off alone and are left
    out."""
    prog, ref = leaf_norms(program_delta), leaf_norms(reference_delta)
    median = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], median)
            for k in ref if ref[k] >= 1e-3 * median]
    return max(gaps) if gaps else float("inf")


def tree_sub(a, b):
    return {k: np.asarray(a[k], np.float64) - np.asarray(b[k], np.float64)
            for k in a}


# ------------------------------------------------------- cost, eqs. (4)-(14)

def _rounder(dtype):
    """Rounds each intermediate to ``dtype`` and back: float64 arithmetic
    as a lower precision would carry it (float64 itself: no rounding)."""
    if np.dtype(dtype) == np.float64:
        return lambda x: np.asarray(x, np.float64)
    return lambda x: np.asarray(np.asarray(x, np.float64).astype(dtype),
                                np.float64)


def device_costs(system, fleet, sched, assign, b, f, model_bits,
                 dtype=np.float64):
    """Per-device time and energy of one edge iteration, eqs. (4)-(8),
    for the allocation (b, f) returned."""
    q = _rounder(dtype)
    u, D, p = q(fleet.u[sched]), q(fleet.D[sched]), q(fleet.p[sched])
    g = q(fleet.g[sched, assign])
    b = q(np.maximum(np.asarray(b, np.float64), 1.0))
    f = q(f)
    n0 = 10.0 ** ((system["noise_dbm_hz"] - 30.0) / 10.0)
    L, alpha = system["L"], system["alpha"]
    rate = q(b * q(np.log2(q(1.0 + q(q(g * p) / q(n0 * b))))))
    t = q(q(L * q(u * D) / f) + q(model_bits / rate))
    e = q(q(alpha / 2.0 * L * q(f ** 2) * q(u * D)) + q(p * model_bits / rate))
    return t, e


def round_cost(system, fleet, sched, assign, b, f, model_bits,
               dtype=np.float64):
    """(T_i, E_i) of eqs. (9)-(14) for the allocation returned."""
    q = _rounder(dtype)
    M, Q = len(fleet.B_m), system["Q"]
    t, e = device_costs(system, fleet, sched, assign, b, f, model_bits,
                        dtype)
    n0 = 10.0 ** ((system["noise_dbm_hz"] - 30.0) / 10.0)
    p_m = 10.0 ** ((system["p_edge_dbm"] - 30.0) / 10.0)
    B = system["cloud_bw"]
    t_cloud = q(model_bits / q(B * np.log2(1.0 + q(fleet.g_cloud) * p_m
                                             / (n0 * B))))
    T_m = t_cloud.copy()
    E_m = q(p_m * t_cloud)
    for m in range(M):
        sel = assign == m
        if sel.any():
            T_m[m] = q(T_m[m] + Q * t[sel].max())
            E_m[m] = q(E_m[m] + q(Q * q(e[sel].sum())))
    return float(T_m.max()), float(q(E_m.sum()))


def rel_gap(program, reference):
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    return float(np.max(np.abs(program - reference) / np.abs(reference)))


def plan_violations(system, fleet, sched, assign, b, f):
    """Broken guarantees of one round's plan: scheduled ids repeated or
    out of range, edges out of range, an edge's bandwidth over its budget
    (beyond float32 rounding), frequencies outside (0, f_max]."""
    N, M = len(fleet.D), len(fleet.B_m)
    sched, assign = np.asarray(sched), np.asarray(assign)
    b, f = np.asarray(b, np.float64), np.asarray(f, np.float64)
    bad = len(sched) - len(np.unique(sched))
    bad += int(np.sum((sched < 0) | (sched >= N)))
    bad += int(np.sum((assign < 0) | (assign >= M)))
    used = np.bincount(assign, weights=b, minlength=M)[:M]
    bad += int(np.sum(used > fleet.B_m * (1 + 1e-5)))
    bad += int(np.sum((b < 0) | (f <= 0) | (f > system["f_max"] * (1 + 1e-6))))
    return bad


# ------------------------------------------- resource allocation, problem (27)

def _edge_rows(system, fleet, sched, assign, model_bits):
    """The cohort laid out per edge: (M, n) arrays of the cycles per edge
    iteration c = L u D, the SNR bandwidth S = g p / N0, the power p, and
    the member mask, with ``rows[m, k]`` the k-th member of edge m."""
    sched, assign = np.asarray(sched), np.asarray(assign)
    M = len(fleet.B_m)
    members = [np.flatnonzero(assign == m) for m in range(M)]
    n = max(1, max(len(r) for r in members))
    rows = np.zeros((M, n), np.int64)
    mask = np.zeros((M, n), bool)
    for m, r in enumerate(members):
        rows[m, :len(r)], mask[m, :len(r)] = r, True
    n0 = 10.0 ** ((system["noise_dbm_hz"] - 30.0) / 10.0)
    dev = sched[rows]
    c = system["L"] * fleet.u[dev] * fleet.D[dev]
    S = fleet.g[dev, np.arange(M)[:, None]] * fleet.p[dev] / n0
    return rows, mask, c, S, fleet.p[dev]


def _tau(b, S, z):
    """Eq. (7): upload time of z bits over bandwidth b."""
    return z / (b * np.log2(1.0 + S / b))


def _dtau(b, S, z):
    x = S / b
    r = b * np.log2(1.0 + x)
    dr = (np.log1p(x) - x / (1.0 + x)) / np.log(2.0)
    return -z * dr / r ** 2


def edge_objectives(system, fleet, sched, assign, b, f, model_bits):
    """(M,) objective of problem (27) that the allocation (b, f) reaches
    at each edge: Q sum_n E_n + lambda Q max_n T_n, cloud terms left out
    (they do not depend on the allocation). NaN for an edge with no
    member."""
    rows, mask, c, S, p = _edge_rows(system, fleet, sched, assign,
                                     model_bits)
    b = np.maximum(np.asarray(b, np.float64)[rows], 1.0)
    f = np.asarray(f, np.float64)[rows]
    tau = _tau(b, S, model_bits)
    t = c / f + tau
    e = system["alpha"] / 2.0 * c * f ** 2 + p * tau
    Q, lam = system["Q"], system["lam"]
    J = (Q * np.sum(np.where(mask, e, 0.0), axis=1)
         + lam * Q * np.max(np.where(mask, t, 0.0), axis=1))
    return np.where(mask.any(axis=1), J, np.nan)


def _log_bisect(lo, hi, below, iters):
    """Geometric bisection, elementwise: the point where ``below(x)``
    turns from true (at ``lo``) to false (at ``hi``)."""
    for _ in range(iters):
        mid = np.sqrt(lo * hi)
        go = below(mid)
        lo, hi = np.where(go, mid, lo), np.where(go, hi, mid)
    return lo, hi


def optimal_allocation(system, fleet, sched, assign, model_bits,
                       grid=16, levels=7, iters=40):
    """The least objective of problem (27) at each edge, in float64, and
    the allocation that reaches it: ((M,) objectives, NaN for an edge
    with no member; (H,) bandwidths; (H,) frequencies).

    Every member finishes exactly at the edge's deadline T, since energy
    grows with f: f_n = c_n / (T - tau_n(b_n)), feasible while f_n <= f_max.
    For a deadline T the energies are convex in the bandwidths, and
    sum_n b_n = B_m is met by bisection on its multiplier mu, each b_n by
    bisection on dE_n/db_n = -mu. The objective is convex in T; T is
    found on a grid that zooms in on its least point, between the
    deadline each member would need with the whole band and the one that
    the even split at f_max pays for."""
    rows, mask, c, S, p = _edge_rows(system, fleet, sched, assign,
                                     model_bits)
    z, alpha = model_bits, system["alpha"]
    Q, lam, f_max = system["Q"], system["lam"], system["f_max"]
    M, n = mask.shape
    B = np.asarray(fleet.B_m, np.float64)
    n_mem = np.maximum(mask.sum(axis=1), 1)
    # the even split at f_max bounds the optimum: lam Q T* <= J*
    b_even = np.broadcast_to((B / n_mem)[:, None], (M, n))
    tau_even = _tau(b_even, S, z)
    J_even = (Q * np.sum(np.where(mask, alpha / 2.0 * c * f_max ** 2
                                  + p * tau_even, 0.0), axis=1)
              + lam * Q * np.max(np.where(mask, c / f_max + tau_even, 0.0),
                                 axis=1))
    t_lo = np.max(np.where(mask, c / f_max + _tau(B[:, None], S, z), 0.0),
                  axis=1)
    t_hi = np.maximum(J_even / (lam * Q), t_lo * (1.0 + 1e-9))

    # (M, G, n) from here on: edges, deadlines, members
    cc, SS, pp, mm = (a[:, None, :] for a in (c, S, p, mask))
    BB = B[:, None, None]

    def best(T):
        """(M, G) objectives at deadlines T, and the bandwidths."""
        slack = T[..., None] - cc / f_max          # time left to upload
        ok = np.all(~mm | (slack > _tau(BB, SS, z)), axis=-1)
        slack = np.where(mm & ok[..., None], slack, 1.0)
        S_ = np.where(mm, SS, 1.0)
        full = np.broadcast_to(BB, slack.shape)
        # least bandwidth that meets the deadline at f_max
        _, b_min = _log_bisect(full * 1e-12, full.copy(),
                               lambda x: _tau(x, S_, z) > slack, iters)

        def slope(b):
            tau = _tau(b, S_, z)
            return (alpha * cc ** 3 / (T[..., None] - tau) ** 3
                    + pp) * _dtau(b, S_, z)

        def bands(mu):
            mu = mu[..., None]
            _, b = _log_bisect(b_min, full.copy(),
                               lambda x: slope(x) + mu < 0, iters)
            b = np.where(slope(b_min) + mu >= 0, b_min, b)
            return np.where(mm, b, 0.0)

        G = T.shape[1]
        _, mu = _log_bisect(
            np.full((M, G), 1e-40), np.full((M, G), 1e6),
            lambda mu: np.sum(bands(mu), axis=-1) > B[:, None], iters)
        b = np.where(mm, bands(mu), 1.0)
        tau = _tau(b, S_, z)
        e = alpha / 2.0 * cc ** 3 / (T[..., None] - tau) ** 2 + pp * tau
        J = Q * np.sum(np.where(mm, e, 0.0), axis=-1) + lam * Q * T
        feasible = ok & (np.sum(np.where(mm, b_min, 0.0), axis=-1)
                         <= B[:, None])
        return np.where(feasible, J, np.inf), b

    lo, hi = t_lo, t_hi
    J_best = np.full(M, np.inf)
    T_best, b_best = np.zeros(M), np.ones((M, n))
    edges = np.arange(M)
    with np.errstate(all="ignore"):
        for _ in range(levels):
            T = lo[:, None] + (hi - lo)[:, None] * np.linspace(0, 1, grid)
            J, b = best(T)
            k = np.argmin(J, axis=1)
            better = J[edges, k] < J_best
            J_best = np.where(better, J[edges, k], J_best)
            T_best = np.where(better, T[edges, k], T_best)
            b_best = np.where(better[:, None], b[edges, k], b_best)
            lo = T[edges, np.maximum(k - 1, 0)]
            hi = T[edges, np.minimum(k + 1, grid - 1)]
        f_best = c / (T_best[:, None] - _tau(b_best, S, z))
    H = len(np.asarray(assign))
    b_dev, f_dev = np.ones(H), np.full(H, f_max)
    b_dev[rows[mask]], f_dev[rows[mask]] = b_best[mask], f_best[mask]
    return np.where(mask.any(axis=1), J_best, np.nan), b_dev, f_dev


def even_allocation(system, fleet, assign, f):
    """Each edge's band split evenly among its members, every member at
    frequency ``f``: at ``f = f_max`` the paper's uniform baseline."""
    assign = np.asarray(assign)
    n_mem = np.bincount(assign, minlength=len(fleet.B_m))
    return fleet.B_m[assign] / n_mem[assign], np.full(len(assign), f)


# ----------------------------------------------- D3QN assignment (Sec. IV)

def d3qn_init(key, feat_dim, n_actions, hidden):
    """The agent's weights: a BiLSTM over the cohort's feature sequence
    (gates in the order input, forget, cell, output), a dense trunk, and
    dueling value and advantage heads. He-normal matrices, zero biases,
    the recurrent matrices scaled by 0.3."""
    ks = jax.random.split(key, 7)

    def dense(k, d_in, d_out):
        return (jax.random.normal(k, (d_in, d_out), jnp.float32)
                * np.sqrt(2.0 / d_in).astype(np.float32))

    def lstm(k1, k2):
        return {"wx": dense(k1, feat_dim, 4 * hidden),
                "wh": dense(k2, hidden, 4 * hidden) * 0.3,
                "b": jnp.zeros((4 * hidden,), jnp.float32)}

    return {"bilstm": {"fwd": lstm(ks[0], ks[1]), "bwd": lstm(ks[2], ks[3])},
            "trunk": {"w": dense(ks[4], 2 * hidden, hidden),
                      "b": jnp.zeros((hidden,), jnp.float32)},
            "v_head": {"w": dense(ks[5], hidden, 1),
                       "b": jnp.zeros((1,), jnp.float32)},
            "a_head": {"w": dense(ks[6], hidden, n_actions),
                       "b": jnp.zeros((n_actions,), jnp.float32)}}


def d3qn_features(fleet, sched):
    """Eq. (24): the cohort's gains to every edge in dB, cycles per
    sample, data size and power, each min-max normalised over the
    cohort."""
    sched = np.asarray(sched)
    feats = np.concatenate(
        [10.0 * np.log10(np.maximum(fleet.g[sched], 1e-30)),
         fleet.u[sched, None], fleet.D[sched, None], fleet.p[sched, None]],
        axis=1)
    lo, hi = feats.min(axis=0), feats.max(axis=0)
    return (feats - lo) / np.maximum(hi - lo, 1e-12)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _lstm(w, xs):
    """Hidden state after each step of ``xs``; the forget gate carries a
    bias of 1."""
    hidden = w["wh"].shape[0]
    h, c, out = np.zeros(hidden), np.zeros(hidden), []
    for x in xs:
        i, fg, g, o = np.split(x @ w["wx"] + w["b"] + h @ w["wh"], 4)
        c = _sigmoid(fg + 1.0) * c + _sigmoid(i) * np.tanh(g)
        h = _sigmoid(o) * np.tanh(c)
        out.append(h)
    return np.stack(out)


def d3qn_q(params, feats):
    """Eq. (20): Q(s_t, a) of every slot t of the cohort, in float64."""
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    enc = np.concatenate([_lstm(w["bilstm"]["fwd"], feats),
                          _lstm(w["bilstm"]["bwd"], feats[::-1])[::-1]],
                         axis=1)
    z = np.maximum(enc @ w["trunk"]["w"] + w["trunk"]["b"], 0.0)
    v = z @ w["v_head"]["w"] + w["v_head"]["b"]
    a = z @ w["a_head"]["w"] + w["a_head"]["b"]
    return v + a - a.mean(axis=1, keepdims=True)


def edge_distances(fleet, sched):
    """(H, M) distances from each scheduled device to each edge [km]."""
    return np.linalg.norm(fleet.dev_pos[np.asarray(sched)][:, None]
                          - fleet.edge_pos[None], axis=-1)


def choice_gap(scores, chosen):
    """Widest gap by which the score of a chosen action lies below the
    best one, over the median spread of the scores across actions."""
    scores = np.asarray(scores, np.float64)
    chosen = np.asarray(chosen)
    lost = scores.max(axis=1) - scores[np.arange(len(chosen)), chosen]
    spread = np.median(scores.max(axis=1) - scores.min(axis=1))
    return float(lost.max() / max(spread, 1e-300))


# ------------------------------------------------- streaming event order

def staleness(events, assign, n_edges):
    """Each flush's staleness and the order's broken rules, worked out
    from the recorded order of dispatches and flushes alone.

    A lane dispatched when its edge had been flushed v times and
    delivered at that edge's flush number V has staleness V - v. Broken
    rules: a lane delivered with no dispatch since its last delivery, or
    to an edge it is not assigned to; a flush's members other than the
    edge's lanes. Returns (list of staleness rows, one per flush, in
    event order; number of broken rules)."""
    assign = np.asarray(assign)
    H = len(assign)
    flushes = np.zeros(n_edges, np.int64)
    start = np.full(H, -1, np.int64)
    rows, broken = [], 0
    for ev in events:
        if ev[0] == "dispatch":
            lanes = np.flatnonzero(ev[1])
            start[lanes] = flushes[assign[lanes]]
            continue
        _, m, delivered, members = ev[:4]
        delivered, members = np.asarray(delivered), np.asarray(members)
        broken += int(np.sum(members != (assign == m)))
        broken += int(np.sum(delivered & ((assign != m) | (start < 0))))
        rows.append(np.where(delivered & (start >= 0),
                             flushes[m] - start, 0).astype(np.float32))
        start[delivered] = -1
        flushes[m] += 1
    return rows, broken
