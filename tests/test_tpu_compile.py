"""Compile-only checks for a described TPU v5e chip, at the paper's width.

Interpret-mode tests cannot see what the chip's compiler refuses (tile
alignment, fast-memory limits, programs too large for the chip). These
tests compile the main path's Pallas kernels — and the kernel-routed
fused round — for one chip of a described ``v5e:2x2`` topology with
``interpret=False`` and check that a Mosaic kernel (``tpu_custom_call``)
is in the compiled program, and that local training's convolutions
lower to the compiler's own convolution. Nothing runs: no result or
time comes from here. Widths are the paper's: M=5 edges, H=50 scheduled
devices, the 114,383-parameter CNN, N=100 devices for K-means, D_n up
to 700.

The topology is described inside a module fixture (never at import),
so every pytest-xdist worker collects the same tests and only the one
given this file loads the TPU compiler.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

M, H, N = 5, 50, 100
P_CNN = 114_383          # paper CNN, 28x28x1 input
D_MAX = 700              # SystemParams().d_range upper end


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_masked_aggregate_compiles_for_v5e(one_chip, no_compile_cache):
    from repro.kernels.hier_agg.hier_agg import (
        masked_aggregate_batched_pallas)
    f32 = jnp.float32
    compiled = masked_aggregate_batched_pallas.lower(
        _spec((1, M, H), f32, one_chip), _spec((1, H), f32, one_chip),
        _spec((1, H, P_CNN), f32, one_chip), interpret=False).compile()
    _assert_kernel(compiled)


def test_int8_decode_aggregate_compiles_for_v5e(one_chip, no_compile_cache):
    from repro.kernels.hier_agg.hier_agg import (
        masked_decode_aggregate_batched_pallas)
    f32 = jnp.float32
    compiled = masked_decode_aggregate_batched_pallas.lower(
        _spec((1, M, H), f32, one_chip), _spec((1, H), f32, one_chip),
        _spec((1, H), f32, one_chip),
        _spec((1, H, P_CNN), jnp.int8, one_chip), interpret=False).compile()
    _assert_kernel(compiled)


def test_kmeans_dist_compiles_for_v5e(one_chip, no_compile_cache):
    from repro.kernels.kmeans_dist.kmeans_dist import (
        pairwise_sq_dists_pallas)
    compiled = pairwise_sq_dists_pallas.lower(
        _spec((N, P_CNN), jnp.float32, one_chip),
        _spec((10, P_CNN), jnp.float32, one_chip),
        interpret=False).compile()
    _assert_kernel(compiled)


def test_kernel_round_step_compiles_for_v5e(one_chip, no_compile_cache,
                                            monkeypatch):
    """The ``agg_kernel=True`` fused round at the paper's setting fits
    one chip. The kernel wrappers pick interpret mode from the default
    backend, which is the CPU here, so the test steers them to the
    compiled kernel."""
    from repro.core import cost_model as cm
    from repro.core.framework import round_step
    from repro.kernels.hier_agg import ops as agg_ops
    from repro.models.cnn import cnn_init
    from repro.models.spec import cnn_spec

    monkeypatch.setattr(agg_ops, "_default_interpret", lambda: False)
    f32 = jnp.float32
    params = jax.eval_shape(
        lambda k: cnn_init(k, (28, 28), 1), jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(params)) == P_CNN
    params = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                          params)
    sp = cm.SystemParams(n_devices=N, n_edges=M,
                         model_bits=float(P_CNN * 32))
    vec = _spec((H,), f32, one_chip)
    compiled = round_step.lower(
        cnn_spec().apply_fn, sp, (params, (None, None)), vec, vec, vec,
        _spec((H, M), f32, one_chip), _spec((M,), f32, one_chip),
        _spec((M,), f32, one_chip),
        _spec((H, D_MAX, 28, 28, 1), f32, one_chip),
        _spec((H, D_MAX), jnp.int32, one_chip),
        _spec((H, D_MAX), f32, one_chip), vec,
        _spec((H,), jnp.int32, one_chip), 0.01,
        M=M, L=sp.L, Q=sp.Q, alloc_steps=200, agg_kernel=True).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9


def test_local_train_convs_lower_natively_for_v5e(one_chip, no_compile_cache):
    """On the TPU ``models.cnn._conv`` lowers conv2 (15 input channels)
    to the compiler's own convolution and keeps im2col for conv1 (one
    input channel): one local step of the paper-width cohort (L=1)
    holds exactly conv2's ``conv_general_dilated`` and its two
    gradients, no f32 im2col patch tensor, and moves well under the
    85.85e9 bytes that im2col for both convs compiles to (32.3e9)."""
    from repro.core.local_train import cohort_local_sgd
    from repro.models.cnn import cnn_apply, cnn_init

    f32 = jnp.float32
    params = jax.eval_shape(
        lambda k: cnn_init(k, (28, 28), 1), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: _spec((H,) + x.shape, x.dtype, one_chip), params)
    step = jax.jit(lambda p, X, y, m: cohort_local_sgd(
        cnn_apply, p, X, y, m, L=1, lr=0.01))
    lowered = step.lower(
        params, _spec((H, D_MAX, 28, 28, 1), f32, one_chip),
        _spec((H, D_MAX), jnp.int32, one_chip),
        _spec((H, D_MAX), f32, one_chip))
    module = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', module, re.M))
    convs = [re.search(r"loc\((#loc\d+)\)$", line.strip()).group(1)
             for line in module.splitlines()
             if "stablehlo.convolution" in line]
    assert len(convs) == 3, convs
    assert all(locs[c].endswith("conv_general_dilated") for c in convs)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert f"f32[{H},{D_MAX},8,8,375]" not in text
    assert f"f32[{H},{D_MAX},24,24,25]" not in text
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 50e9
