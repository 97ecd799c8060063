"""What the drivers hand the system under test, and the spans around it.

The world is the benchmark's own (``world.py``); here it is put into the
program's types. The weights are made here too, on the device in one
jitted call each from the seed, and replace the program's own
initialisation, so that the reference starts from the same weights
without taking any that the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, world as wd


def system_params(config):
    """The program's ``SystemParams`` for a configuration file."""
    from repro.core import cost_model as cm
    s = config["system"]
    return cm.SystemParams(
        n_devices=s["n_devices"], n_edges=s["n_edges"],
        area_km=s["area_km"], u_range=tuple(s["u_range"]),
        d_range=tuple(s["d_range"]),
        edge_bw_range=tuple(s["edge_bw_range"]), cloud_bw=s["cloud_bw"],
        p_dbm_range=tuple(s["p_dbm_range"]), p_edge_dbm=s["p_edge_dbm"],
        f_max=s["f_max"], noise_dbm_hz=s["noise_dbm_hz"], alpha=s["alpha"],
        shadow_db=s["shadow_db"], L=s["L"], Q=s["Q"], lam=s["lam"])


def population(config, fleet):
    from repro.core import cost_model as cm
    N = len(fleet.D)
    return cm.Population(
        u=jnp.asarray(fleet.u), D=jnp.asarray(fleet.D),
        p=jnp.asarray(fleet.p),
        f_max=jnp.full((N,), config["system"]["f_max"]),
        g=jnp.asarray(fleet.g), g_cloud=jnp.asarray(fleet.g_cloud),
        B_m=jnp.asarray(fleet.B_m), dev_pos=fleet.dev_pos,
        edge_pos=fleet.edge_pos)


def federated(config, w):
    from repro.data.partition import FederatedData
    return FederatedData(list(w.Xs), list(w.ys), w.majority, w.X_test,
                         w.y_test, config["data"]["n_classes"])


def model_weights(config, seed):
    """The CNN's weights from ``seed``, float32 as the program serves them."""
    m = config["model"]
    init = jax.jit(functools.partial(
        reference.cnn_init, image_hw=tuple(m["image_hw"]),
        channels=m["channels"], conv1=m["conv1"], conv2=m["conv2"],
        kernel=m["kernel"], hidden=m["hidden"], n_classes=m["n_classes"]))
    return init(jax.random.PRNGKey(wd.sub_seed(seed, 6)))


def d3qn_weights(config, seed):
    """Untrained D3QN weights at the trainer's default width, from the
    reference's own initialisation: one greedy dispatch costs the same
    whatever the weights are."""
    M = config["system"]["n_edges"]
    init = jax.jit(reference.d3qn_init, static_argnums=(1, 2, 3))
    return init(jax.random.PRNGKey(wd.sub_seed(seed, 7)), M + 3, M,
                config["assignment"]["d3qn_hidden"])


def host(tree):
    """A float32 numpy copy of a tree of device arrays."""
    return jax.tree.map(lambda v: np.asarray(v, np.float32), tree)


def spanned(name, fn):
    """``fn`` inside a host span of the profiler's trace."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)
    return call


class Recorder:
    """Wraps a module's jitted function: a host span around each call,
    the last call's arguments in ``last`` and, while ``on``,
    ``keep(args, kwargs, result)`` appended to ``calls`` (a list that
    several recorders may share, to keep their order)."""

    def __init__(self, module, attr, span, keep, calls=None):
        self.module, self.attr = module, attr
        self.fn = getattr(module, attr)
        self.calls = [] if calls is None else calls
        self.on, self.last = False, None

        def call(*args, **kwargs):
            with jax.profiler.TraceAnnotation(span):
                out = self.fn(*args, **kwargs)
            self.last = (args, kwargs)
            if self.on:
                self.calls.append(keep(args, kwargs, out))
            return out

        setattr(module, attr, call)

    def restore(self):
        setattr(self.module, self.attr, self.fn)
