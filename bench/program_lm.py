"""The LoRA payload's weights in the program's types, and back.

The benchmark makes the weights in the reference's layout
(``reference_lm``); here they are laid out as the program holds them:
the frozen base as ``models/transformer.py`` initialises it (layer
templates of one period, stacked over the periods; Mamba-2's conv of
[x|B|C] split per segment) and the trainable tree as ``models/lora.py``
does. The base's matrices, embedding and conv are rounded to the
configuration's base dtype first, so that the reference reads exactly
the values the program holds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# reference name -> program name of each adapted matrix, by group
MIX = {"mamba": {"in_z": "wz", "in_x": "wx", "in_B": "wb", "in_C": "wc",
                 "in_dt": "wdt", "out": "out_proj"},
       "attention": {"q": "wq", "k": "wk", "v": "wv", "o": "wo"}}
MLP = {"gate": "w_gate", "up": "w_up", "down": "w_down"}
FULL = ("norm1", "norm2", "final_norm", "gate_norm", "A_log", "D",
        "dt_bias")      # held in float32 whatever the base dtype


def held(weights, dtype):
    """The base in the dtypes the program holds it in: ``dtype`` for
    every leaf but those of ``FULL``, which stay float32."""
    def cast(path, a):
        name = jax.tree_util.keystr(path[-1:]).strip("[]'\"")
        return a if name in FULL else a.astype(dtype)
    return jax.tree_util.tree_map_with_path(cast, weights)


def _period(m):
    """The shortest period of the layer pattern (the program's
    super-block): layer i is template i % period."""
    n, kinds = len(m.layer_types), m.layer_types
    return min(p for p in range(1, n + 1) if n % p == 0
               and all(kinds[i] == kinds[i % p] for i in range(n)))


def _stack(layers, m, fn):
    """Program blocks: template j of the period, stacked over periods."""
    p = _period(m)
    return [jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[fn(layers[i], m.layer_types[i])
                           for i in range(j, len(layers), p)])
            for j in range(p)]


def frozen(weights, m, dtype):
    """The program's frozen tree of reference base ``weights``."""
    def layer(w, kind):
        lo = lambda a: a.astype(dtype)  # noqa: E731
        if kind == "mamba":
            di, gn = m.d_inner, m.groups * m.d_state
            cw, cb = w["conv_w"], w["conv_b"]
            mix = {"A_log": w["A_log"], "D_skip": w["D"],
                   "dt_bias": w["dt_bias"],
                   "gate_norm": {"scale": w["gate_norm"]},
                   "conv_x": lo(cw[:di]), "conv_b": lo(cw[di:di + gn]),
                   "conv_c": lo(cw[di + gn:]), "conv_x_bias": lo(cb[:di]),
                   "conv_b_bias": lo(cb[di:di + gn]),
                   "conv_c_bias": lo(cb[di + gn:])}
        else:
            mix = {}
        mix.update({p: lo(w[r]) for r, p in MIX[kind].items()})
        return {"norm1": {"scale": w["norm1"]},
                "norm2": {"scale": w["norm2"]}, "mix": mix,
                "mlp": {p: lo(w[r]) for r, p in MLP.items()}}

    return {"embed": weights["embed"].astype(dtype),
            "final_norm": {"scale": weights["final_norm"]},
            "blocks": _stack(weights["layers"], m, layer)}


def params(ad, m):
    """The program's trainable tree of reference adapters ``ad``."""
    def layer(a, kind):
        return {"mix": {p: a[r] for r, p in MIX[kind].items()},
                "mlp": {p: a[r] for r, p in MLP.items()}}
    return {"blocks": _stack(ad["layers"], m, layer),
            "cls_head": ad["head"]}


def adapters(prog, m):
    """Reference adapters of the program's trainable tree ``prog``."""
    p = _period(m)
    layers = []
    for i, kind in enumerate(m.layer_types):
        t = jax.tree.map(lambda a: np.asarray(a[i // p], np.float32),
                         prog["blocks"][i % p])
        names = {**{p_: r for r, p_ in MIX[kind].items()},
                 **{p_: r for r, p_ in MLP.items()}}
        layers.append({names[k]: v for g in ("mix", "mlp")
                       for k, v in t[g].items()})
    return {"layers": layers,
            "head": np.asarray(prog["cls_head"], np.float32)}
