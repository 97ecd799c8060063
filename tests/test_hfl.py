"""HFL trainer (Algorithm 1, eqs. 2-3): aggregation math + learning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hfl import (evaluate_in_batches, hfl_global_iteration,
                            pad_device_data)
from repro.data import make_dataset, partition_noniid
from repro.models import cnn

KEY = jax.random.PRNGKey(0)


def _linear_apply(params, X):
    return X.reshape(X.shape[0], -1) @ params["w"]


def test_edge_and_cloud_aggregation_weights():
    """With L chosen so locals stay put (lr=0), the aggregate must be the
    D_n-weighted mean of identical models = the global model itself."""
    w0 = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1, (4, 3)))}
    H, Dmax = 6, 5
    X = jnp.zeros((H, Dmax, 2, 2, 1))
    y = jnp.zeros((H, Dmax), jnp.int32)
    mask = jnp.ones((H, Dmax))
    sizes = jnp.asarray([1., 2., 3., 4., 5., 6.])
    assign = jnp.asarray([0, 0, 1, 1, 2, 2])
    out, _ = hfl_global_iteration(_linear_apply, w0, X, y, mask, sizes,
                                  assign, M=3, L=2, Q=2, lr=0.0)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(w0["w"]),
                               atol=1e-6)


def test_single_device_single_edge_equals_local_sgd():
    """H=1, M=1: HFL reduces to plain local training (eq. 16 telescoping)."""
    from repro.core.local_train import local_sgd
    rng = np.random.default_rng(0)
    X1 = jnp.asarray(rng.normal(0, 1, (1, 8, 2, 2, 1)).astype(np.float32))
    y1 = jnp.asarray(rng.integers(0, 3, (1, 8)).astype(np.int32))
    m1 = jnp.ones((1, 8))
    w0 = {"w": jnp.asarray(rng.normal(0, 0.1, (4, 3)).astype(np.float32))}
    out, _ = hfl_global_iteration(_linear_apply, w0, X1, y1, m1,
                                  jnp.ones(1), jnp.zeros(1, jnp.int32),
                                  M=1, L=3, Q=2, lr=0.05)
    # manual: Q rounds of (L local steps from the aggregated model)
    w = w0
    for _ in range(2):
        w = local_sgd(_linear_apply, w, X1[0], y1[0], m1[0], 3, 0.05)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(w["w"]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_hfl_cnn_learns_synthetic():
    """A few global iterations must beat chance on the synthetic dataset."""
    X, y, Xt, yt = make_dataset("fmnist_syn", n_train=1500, n_test=400, seed=0)
    fed = partition_noniid(X, y, Xt, yt, n_devices=12, size_range=(40, 60),
                           seed=0)
    Xp, yp, mask = pad_device_data(fed)
    params = cnn.cnn_init(KEY, (28, 28), 1)
    sched = np.arange(12)
    assign = np.asarray(sched % 3)
    acc0 = evaluate_in_batches(cnn.cnn_apply, params, fed.X_test, fed.y_test)
    for _ in range(3):
        params, _ = hfl_global_iteration(
            cnn.cnn_apply, params, Xp[sched], yp[sched], mask[sched],
            jnp.asarray(fed.sizes[sched], jnp.float32), jnp.asarray(assign),
            M=3, L=3, Q=2, lr=0.02)   # lr=0.05 diverges on this tiny split
    acc1 = evaluate_in_batches(cnn.cnn_apply, params, fed.X_test, fed.y_test)
    assert acc1 > max(acc0, 0.15)


def test_empty_edge_keeps_model_valid():
    w0 = {"w": jnp.ones((4, 3))}
    H, Dmax = 2, 4
    X = jnp.zeros((H, Dmax, 2, 2, 1))
    y = jnp.zeros((H, Dmax), jnp.int32)
    mask = jnp.ones((H, Dmax))
    out, _ = hfl_global_iteration(_linear_apply, w0, X, y, mask,
                                  jnp.ones(H), jnp.zeros(H, jnp.int32),
                                  M=3, L=1, Q=1, lr=0.0)   # edges 1,2 empty
    assert bool(jnp.all(jnp.isfinite(out["w"])))
