"""Each cell's driver, at a tiny size on the CPU, called as a function:
its records, the keys of the line it would print, and the comparison
that decides ``correct`` - true for the program as it is, false for the
control and for each fault planted under the timed path."""
import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import tiny

SYNC = ("paper-fmnist.sync-h50", "paper-cifar10.sync-h25")
STREAM = ("paper-fmnist.stream-diurnal",)
SEED = 2**31 + 12345


def execute(name, trace=0):
    spec, cell, config = tiny.cell(name)
    return run.execute(spec, name, cell, config, SEED, 0.5, trace,
                       jax.devices()[:1])


@pytest.mark.parametrize("name", SYNC + STREAM)
def test_rehearsal_is_correct_and_prints_no_device_metric(name):
    line = execute(name)
    assert line["correct"] is True, line["checked"]
    assert list(line)[-1] == "checked"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    e2e = {m["name"] for m in run.metrics_for(
        run.load_json(run.ROOT, "BENCHMARK.json"), name, 0)}
    assert set(line["rehearsal"]) == e2e


@pytest.mark.parametrize("name", SYNC[:1] + STREAM)
def test_traced_rehearsal_reads_host_metrics_only(name):
    line = execute(name, trace=1)
    assert line["correct"] is True, line["checked"]
    assert "breakdown" not in line
    # no TPU plane in a CPU trace: every device reading stays silent
    assert all(not k.startswith(("device_idle", "mfu", "round_step_ms",
                                 "dispatch_ms"))
               for k in line["rehearsal"])


def _driver(name):
    import importlib
    _, cell, config = tiny.cell(name)
    mod = importlib.import_module(f"bench.drivers.{cell['driver']}")
    return mod, cell, config


@pytest.mark.parametrize("name", SYNC[:1] + STREAM)
@pytest.mark.parametrize("kind", ["control", "half", "alloc0", "assign0"])
def test_reference_put_in_the_programs_place_is_not_correct(name, kind):
    mod, cell, config = _driver(name)
    d = mod.Driver(config, cell["params"], SEED)
    d.free()
    ok, checked = run.judge(d.check(kind), cell["limits"])
    assert not ok, checked


def _unchanged_round_step(fn):
    def call(apply_fn, sp, params, *a, **k):
        _, aux = fn(apply_fn, sp, params, *a, **k)
        return params, aux
    return call


def _half_round_step(fn):
    def call(apply_fn, sp, params, u, D, p, g, g_cloud, B_m, X, y, mask,
             sizes, *a, **k):
        sizes = sizes.at[sizes.shape[0] // 2:].set(0.0)
        return fn(apply_fn, sp, params, u, D, p, g, g_cloud, B_m, X, y,
                  mask, sizes, *a, **k)
    return call


def _altered_round_step(fn):
    def call(*a, **k):
        params, (T_i, E_i, T_m, E_m, b, f) = fn(*a, **k)
        return params, (T_i * 1.01, E_i, T_m, E_m, b, f)
    return call


def _unchanged_dispatch(fn):
    def call(apply_fn, cohort, *a, **k):
        return cohort
    return call


def _half_flush(fn):
    def call(edge, cohort, m, deliver, members, sizes, *a):
        return fn(edge, cohort, m, deliver, members,
                  sizes.at[sizes.shape[0] // 2:].set(0.0), *a)
    return call


def _half_cloud(fn):
    def call(edge, assign, sizes, **k):
        return fn(edge, assign, sizes.at[sizes.shape[0] // 2:].set(0.0),
                  **k)
    return call


def _alloc_left_at_start(fn):
    def call(*a, **k):
        return fn(*a, **{**k, "alloc_steps": 0})
    return call


def _assign_shifted(fn):
    def call(self, pop, sched, rng=None):
        assign, aux = fn(self, pop, sched, rng)
        return (assign + 1) % pop.n_edges, aux
    return call


def _altered_alloc(fn):
    def call(*a, **k):
        b, f, tc, ec, T_cl, E_cl = fn(*a, **k)
        return b, f, tc.at[0].multiply(1.01), ec, T_cl, E_cl
    return call


FAULTS = {
    "sync": {
        "alloc_at_start": [("repro.core.framework", "round_step",
                            _alloc_left_at_start)],
        "assign_shifted": [("repro.core.assignment.drl",
                            "DRLAssigner.assign", _assign_shifted)],
        "unchanged": [("repro.core.framework", "round_step",
                       _unchanged_round_step)],
        "half": [("repro.core.framework", "round_step", _half_round_step)],
        "altered": [("repro.core.framework", "round_step",
                     _altered_round_step)]},
    "stream": {
        "alloc_at_start": [("repro.core.async_engine", "_alloc_and_price",
                            _alloc_left_at_start)],
        "assign_shifted": [("repro.core.assignment.geo",
                            "GeoAssigner.assign", _assign_shifted)],
        "unchanged": [("repro.core.async_engine", "_train_dispatched",
                       _unchanged_dispatch)],
        "half": [("repro.core.async_engine", "_flush_edge", _half_flush),
                 ("repro.core.async_engine", "_cloud_agg", _half_cloud)],
        "altered": [("repro.core.async_engine", "_alloc_and_price",
                     _altered_alloc)]},
}


@pytest.mark.parametrize("name", SYNC[:1] + STREAM)
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "alloc_at_start", "assign_shifted"])
def test_a_fault_under_the_timed_path_is_not_correct(name, fault,
                                                     monkeypatch):
    import importlib
    _, cell, _ = _driver(name)
    for module, attr, wrap in FAULTS[cell["driver"]][fault]:
        owner = importlib.import_module(module)
        *path, attr = attr.split(".")
        for part in path:
            owner = getattr(owner, part)
        monkeypatch.setattr(owner, attr, wrap(getattr(owner, attr)))
    line = execute(name)
    assert line["correct"] is False, line["checked"]


def test_a_staleness_the_event_order_does_not_give_is_not_correct():
    mod, cell, config = _driver(STREAM[0])
    d = mod.Driver(config, cell["params"], SEED)
    d.free()
    events = d.rounds[0]["events"]
    k = next(i for i, ev in enumerate(events)
             if ev[0] == "flush" and ev[2].any())
    ev = events[k]
    events[k] = ev[:4] + (ev[4] + ev[2],) + ev[5:]
    numbers = d.check()
    assert numbers["event_errors"] == ev[2].sum()
    ok, checked = run.judge(numbers, cell["limits"])
    assert not ok, checked


def test_reference_precision_is_highest_for_float32():
    with __import__("bench.reference", fromlist=["precision"]).precision(
            jnp.float32):
        assert jax.config.jax_default_matmul_precision == "highest"
