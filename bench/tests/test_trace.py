"""The trace reducer on traces with known busy intervals and gaps."""
import json
import os

import pytest

from bench import trace as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1_000_000


def _events():
    """A 100 ms window on one chip: program A runs 10-40 ms (two ops,
    one nested in a loop op), program B 60-90 ms; the host sleeps 40-60
    ms. Device events outside the window are cut off."""
    return [
        (HOST, "python", "bench.window", 0, 100 * MS),
        (HOST, "python", "host.sleep", 40 * MS, 20 * MS),
        (DEV, "XLA Modules", "jit_a(123)", 10 * MS, 30 * MS),
        (DEV, "XLA Ops", "%while.1", 10 * MS, 30 * MS),
        (DEV, "XLA Ops", "%fusion.2", 12 * MS, 5 * MS),
        (DEV, "XLA Modules", "jit_b(456)", 60 * MS, 30 * MS),
        (DEV, "XLA Ops", "%convolution.3", 60 * MS, 30 * MS),
        (DEV, "XLA Modules", "jit_b(456)", 95 * MS, 10 * MS),
        (DEV, "XLA Ops", "%copy.4", 95 * MS, 10 * MS),
        (DEV, "Steps", "1", 10 * MS, 30 * MS),
    ]


def test_busy_programs_and_gaps_of_a_known_trace():
    r = tr.reduce(_events(), ("host.sleep",))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.065)     # 30 + 30 + 5 ms
    assert r["program_s"] == pytest.approx({"a": 0.03, "b": 0.035})
    gaps = dict((round(s, 6), n) for n, s in r["idle_gaps"])
    assert gaps == {0.02: "host.sleep", 0.01: "host", 0.005: "host"}
    bd = tr.breakdown(r)
    assert bd["device_ops"][0] == ["b", pytest.approx(0.035)]
    assert bd["idle_gaps"][0] == ["host.sleep", pytest.approx(0.02)]


def test_no_device_plane_gives_nothing_to_read():
    ev = [e for e in _events() if e[0] == HOST]
    assert tr.reduce(ev, ()) is None


def test_program_names_drop_the_jit_prefix_and_the_id():
    assert tr.program_name("jit_round_step(11366319801257705245)") == \
        "round_step"
    assert tr.program_name("jit__train_dispatched(9)") == "_train_dispatched"


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_trace_small.json")


def test_recorded_chip_trace():
    """A trace recorded on one v5e: a jitted 8-step matmul chain, a
    200 ms host sleep in its own span, the chain again."""
    ev = [tuple(e) for e in json.load(open(RECORDED))]
    r = tr.reduce(ev, ("host.sleep",))
    name, longest = r["idle_gaps"][0]
    assert name == "host.sleep" and longest == pytest.approx(0.2, abs=0.02)
    assert set(r["program_s"]) == {"chain"}
    assert 0 < r["busy_s"] <= r["program_s"]["chain"] + 1e-9
    assert r["busy_s"] + sum(s for _, s in r["idle_gaps"]) == \
        pytest.approx(r["window_s"])
