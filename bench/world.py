"""Seeded deployment worlds: the fleet's data, its population, the traffic.

Everything a run feeds the system is made here from ``--seed``, in numpy
on the host, with no import of the system under test. The generators
follow the system's own recipes (class-prototype synthetic images,
majority-class non-IID partition, the paper's Table-I population and
channel model, Poisson joins with a diurnal rate), copied so that no
change to the program can move what the benchmark feeds it.

One departure, so that every seed gives the same work: the N per-device
data sizes are one fixed set, evenly spaced over ``d_range``, that the
seed only permutes (the paper draws them uniformly). The padded cohort
shapes, and with them the device work of a round, then never change with
the seed. The same size prices a device in the cost model and sizes its
partition.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

IMAGE_NOISE = 0.35
BRIGHTNESS_SIGMA = 0.08
PROTO_SMOOTH = 3
MAJORITY_FRAC = 0.8


def sub_seed(seed: int, tag: int) -> int:
    """A 31-bit seed for stream ``tag`` of run seed ``seed`` (any size)."""
    return int(np.random.SeedSequence([seed, tag]).generate_state(1)[0]
               >> 1)


def _smooth(rng, hw, channels, k):
    """Low-frequency random image in [0, 1]: bilinear upsampled noise."""
    H, W = hw
    coarse = rng.random((k + 2, k + 2, channels))
    ys, xs = np.linspace(0, k + 1, H), np.linspace(0, k + 1, W)
    yi, xi = np.floor(ys).astype(int), np.floor(xs).astype(int)
    yf, xf = ys - yi, xs - xi
    yi1, xi1 = np.minimum(yi + 1, k + 1), np.minimum(xi + 1, k + 1)
    a = (coarse[yi][:, xi] * (1 - yf)[:, None, None]
         + coarse[yi1][:, xi] * yf[:, None, None])
    b = (coarse[yi][:, xi1] * (1 - yf)[:, None, None]
         + coarse[yi1][:, xi1] * yf[:, None, None])
    return a * (1 - xf)[None, :, None] + b * xf[None, :, None]


def images(image_hw, channels, n_classes, n_train, n_test, seed):
    """(X_train, y_train, X_test, y_test): NHWC float32 in [0, 1], one
    smooth prototype per class plus pixel noise and brightness jitter."""
    rng = np.random.default_rng(seed)
    protos = np.stack([_smooth(rng, image_hw, channels, PROTO_SMOOTH)
                       for _ in range(n_classes)])
    rng = np.random.default_rng(seed + 1)

    def draw(n):
        y = rng.integers(0, n_classes, n)
        noise = rng.normal(0, IMAGE_NOISE, (n, *image_hw, channels))
        bright = rng.normal(0, BRIGHTNESS_SIGMA, (n, 1, 1, 1))
        X = np.clip(protos[y] + noise + bright, 0.0, 1.0)
        return X.astype(np.float32), y.astype(np.int32)

    return (*draw(n_train), *draw(n_test))


def device_sizes(n_devices, d_range, seed):
    """The fleet's D_n: N sizes evenly spaced over ``d_range``, permuted."""
    sizes = np.round(np.linspace(d_range[0], d_range[1], n_devices))
    return np.random.default_rng(seed).permutation(sizes.astype(np.int64))


def partition(X, y, sizes, n_classes, seed):
    """Device n holds ``sizes[n]`` samples, ``MAJORITY_FRAC`` of them from
    its majority class (classes dealt round robin, shuffled), the rest
    drawn from the whole set. Returns (X list, y list, majority)."""
    rng = np.random.default_rng(seed)
    by_class = [np.flatnonzero(y == c) for c in range(n_classes)]
    majority = np.arange(len(sizes)) % n_classes
    rng.shuffle(majority)
    Xs, ys = [], []
    for n, d in enumerate(sizes):
        n_major = int(round(MAJORITY_FRAC * d))
        idx = np.concatenate([rng.choice(by_class[majority[n]], n_major),
                              rng.integers(0, len(y), d - n_major)])
        rng.shuffle(idx)
        Xs.append(X[idx])
        ys.append(y[idx])
    return Xs, ys, majority.astype(np.int32)


def pad(Xs, ys, d_max):
    """(N, d_max, ...) samples, (N, d_max) labels and validity mask."""
    N = len(Xs)
    X = np.zeros((N, d_max, *Xs[0].shape[1:]), np.float32)
    y = np.zeros((N, d_max), np.int32)
    mask = np.zeros((N, d_max), np.float32)
    for n in range(N):
        d = len(ys[n])
        X[n, :d], y[n, :d], mask[n, :d] = Xs[n], ys[n], 1.0
    return X, y, mask


def dbm_to_watt(dbm):
    return 10.0 ** ((np.asarray(dbm) - 30.0) / 10.0)


def _gain(rng, dist_km, shadow_db):
    """128.1 + 37.6 log10(d_km) path loss with log-normal shadowing."""
    d = np.maximum(dist_km, 0.01)
    pl_db = 128.1 + 37.6 * np.log10(d)
    return 10 ** (-(pl_db + rng.normal(0.0, shadow_db, d.shape)) / 10.0)


@dataclasses.dataclass
class Fleet:
    """A population in float64 numpy: the paper's Table I."""
    u: np.ndarray            # (N,) CPU cycles per sample
    D: np.ndarray            # (N,) samples
    p: np.ndarray            # (N,) transmit power [W]
    g: np.ndarray            # (N, M) uplink gain to each edge
    g_cloud: np.ndarray      # (M,) edge-to-cloud gain
    B_m: np.ndarray          # (M,) edge bandwidth [Hz]
    dev_pos: np.ndarray      # (N, 2) km
    edge_pos: np.ndarray     # (M, 2) km


def fleet(system, sizes, seed):
    """Devices and edges uniform in the square; cloud at its centre."""
    rng = np.random.default_rng(seed)
    N, M, area = len(sizes), system["n_edges"], system["area_km"]
    dev_pos = rng.uniform(0, area, (N, 2))
    edge_pos = rng.uniform(0, area, (M, 2))
    d_ne = np.linalg.norm(dev_pos[:, None] - edge_pos[None], axis=-1)
    d_mc = np.linalg.norm(edge_pos - np.array([area / 2, area / 2]),
                          axis=-1)
    return Fleet(
        u=rng.uniform(*system["u_range"], N),
        D=np.asarray(sizes, np.float64),
        p=dbm_to_watt(rng.uniform(*system["p_dbm_range"], N)),
        g=_gain(rng, d_ne, system["shadow_db"]),
        g_cloud=_gain(rng, d_mc, system["shadow_db"]),
        B_m=rng.uniform(*system["edge_bw_range"], M),
        dev_pos=dev_pos, edge_pos=edge_pos)


@dataclasses.dataclass
class World:
    """One deployment as the benchmark feeds it to the system."""
    fleet: Fleet
    Xs: list                 # per-device samples
    ys: list                 # per-device labels
    majority: np.ndarray     # (N,) majority class per device
    X_test: np.ndarray
    y_test: np.ndarray
    X: np.ndarray            # (N, Dmax, ...) padded
    y: np.ndarray            # (N, Dmax)
    mask: np.ndarray         # (N, Dmax)


def build_world(config, seed, fleet_seed=None):
    """The deployment of ``config`` (a configuration file's dict) drawn
    from run seed ``seed``. With ``fleet_seed`` the fleet itself (sizes,
    cycles, powers, positions and gains) is the one that seed draws, and
    the run seed draws only the data: every run then has the same
    devices, as a fleet whose day is replayed has."""
    data, system = config["data"], config["system"]
    N = system["n_devices"]
    X, y, X_test, y_test = images(
        tuple(data["image_hw"]), data["channels"], data["n_classes"],
        data["n_train"], data["n_test"], sub_seed(seed, 1))
    fl_seed = seed if fleet_seed is None else fleet_seed
    sizes = device_sizes(N, system["d_range"], sub_seed(fl_seed, 2))
    Xs, ys, majority = partition(X, y, sizes, data["n_classes"],
                                 sub_seed(seed, 3))
    Xp, yp, mask = pad(Xs, ys, int(system["d_range"][1]))
    return World(fleet(system, sizes, sub_seed(fl_seed, 4)), Xs, ys,
                 majority, X_test, y_test, Xp, yp, mask)


def availability(params, n_devices, seed, horizon_s):
    """Fleet availability under Poisson joins with a diurnal (and
    optionally bursty) rate; each join keeps a device online for an
    exponential session. Returns (init_up (N,), toggles (N, T) ascending
    flip times padded with +inf)."""
    rng = np.random.default_rng(seed)
    join = params["join_rate_per_device"] * n_devices
    amp, period = params["diurnal_amp"], params["diurnal_period_s"]
    burst_mult = params.get("burst_mult", 1.0)
    burst_every = params.get("burst_every_s", math.inf)
    burst_len = params.get("burst_len_s", 0.0)

    def rate(t):
        lam = join * (1.0 + amp * math.sin(2.0 * math.pi * t / period))
        if math.isfinite(burst_every) and t % burst_every < burst_len:
            lam *= burst_mult
        return max(lam, 0.0)

    online = rng.uniform(size=n_devices) < params["p_online0"]
    toggles = [[] for _ in range(n_devices)]
    leave = np.full(n_devices, np.inf)
    leave[online] = rng.exponential(params["mean_session_s"],
                                    int(online.sum()))
    init_up = online.copy()
    env = join * (1.0 + max(amp, 0.0)) * max(burst_mult, 1.0)
    t = 0.0
    while True:
        t_join = t + rng.exponential(1.0 / env)
        t_leave = leave.min()
        t = min(t_join, t_leave)
        if t > horizon_s:
            break
        if t_leave <= t_join:
            d = int(leave.argmin())
            online[d], leave[d] = False, np.inf
            toggles[d].append(t)
            continue
        if rng.uniform() * env > rate(t):
            continue
        off = np.flatnonzero(~online)
        if len(off) == 0:
            continue
        d = int(rng.choice(off))
        online[d] = True
        leave[d] = t + rng.exponential(params["mean_session_s"])
        toggles[d].append(t)
    width = max(1, max(len(r) for r in toggles))
    tog = np.full((n_devices, width), np.inf)
    for d, row in enumerate(toggles):
        tog[d, :len(row)] = row
    return init_up, tog
