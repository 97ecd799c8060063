"""The LoRA cell's driver at a tiny size on the CPU, called as
a function: a rehearsal is ``correct``; the reference put in the
program's place (control, half, the plan's faults) and faults planted
under the timed path are not. Also the LoRA payload's operation count
against XLA's cost analysis."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_lm, reference_lm, run

LORA = "granite-4.0-h-micro.sync-h30"
SEED = 2**31 + 12345


def lora_tiny():
    """The LoRA cell at the program's smoke payload: one period of 10
    layers at width 128, 10 devices, 4 scheduled, 32-token sequences."""
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.load_json(run.BENCH, "cells", f"{LORA}.json")
    config = run.load_json(run.BENCH, "configs", f"{cell['config']}.json")
    config.update(hidden_size=128, num_attention_heads=4,
                  num_key_value_heads=2, shared_intermediate_size=256,
                  intermediate_size=256, mamba_n_heads=8, mamba_d_head=32,
                  mamba_d_state=16, mamba_chunk_size=16,
                  attention_multiplier=1.0 / 32, vocab_size=503)
    config["program"]["scale"] = "smoke"
    config["system"].update(n_devices=10, n_edges=3, L=2, Q=2)
    config["data"].update(n_train=60, n_test=8, seq_len=32, topic_size=16)
    config["H"] = 4
    cell["params"].update(check_rounds=2, block=2)
    return spec, cell, config


def execute(trace=0):
    spec, cell, config = lora_tiny()
    return run.execute(spec, LORA, cell, config, SEED, 0.5, trace,
                       jax.devices()[:1])


def test_rehearsal_is_correct_and_prints_no_device_metric():
    line = execute()
    assert line["correct"] is True, line["checked"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    e2e = {m["name"] for m in run.metrics_for(
        run.load_json(run.ROOT, "BENCHMARK.json"), LORA, 0)}
    assert set(line["rehearsal"]) == e2e == {"setup_s", "round_s"}


def test_traced_lora_rehearsal_reads_the_pad_share():
    line = execute(trace=1)
    assert line["correct"] is True, line["checked"]
    # D_n 2-4 over 4 slots: a quarter of the token-steps pad, on average
    assert 0.0 < line["rehearsal"]["pad_share.lora"]["value"] < 50.0
    # the assigner's host span is read as in the CNN's sync cells
    assert line["rehearsal"]["assign_ms.sync"]["value"] > 0.0
    assert not any(k.startswith(("device_idle", "mfu", "round_step_ms"))
                   for k in line["rehearsal"])


@pytest.mark.parametrize("kind", ["control", "half", "alloc0", "assign0"])
def test_reference_put_in_the_programs_place_is_not_correct(kind):
    _, cell, config = lora_tiny()
    mod = importlib.import_module(f"bench.drivers.{cell['driver']}")
    d = mod.Driver(config, cell["params"], SEED)
    d.free()
    ok, checked = run.judge(d.check(kind), cell["limits"])
    assert not ok, checked


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "alloc_at_start", "assign_shifted"])
def test_a_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    from bench.tests.test_drivers import FAULTS
    for module, attr, wrap in FAULTS["sync"][fault]:
        owner = importlib.import_module(module)
        *path, attr = attr.split(".")
        for part in path:
            owner = getattr(owner, part)
        monkeypatch.setattr(owner, attr, wrap(getattr(owner, attr)))
    line = execute()
    assert line["correct"] is False, line["checked"]


def test_lora_operation_count_matches_xla_on_one_adapted_projection():
    """Forward, input gradient and adapter gradients of x W + s (x A) B
    with W frozen: 2 d_in d_out + 3 r (d_in + d_out) MACs a token."""
    n, d_in, d_out, r = 64, 96, 160, 16

    def f(a, b, x, w):
        return jnp.sum((x @ w + 2.0 * ((x @ a) @ b)) ** 2)

    args = [jnp.ones(s) for s in ((d_in, r), (r, d_out), (n, d_in),
                                  (d_in, d_out))]
    step = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
    cost = step.lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    macs = 2 * d_in * d_out + 3 * r * (d_in + d_out)
    # XLA also counts the elementwise square and scale: a few per output
    assert 2 * macs * n <= cost["flops"] <= 2 * macs * n * 1.05


def test_lora_forward_count_matches_xla_at_a_small_size():
    """The whole forward pass of a small period against XLA's count
    (the recurrence left out of XLA's side: the reference's scan body
    is counted once, so it is compared without it)."""
    m = reference_lm.Dims(
        layer_types=("mamba", "attention"), d=64, vocab=50, heads=4,
        kv_heads=2, head_dim=16, ff=128, ssm_heads=4, ssm_head_dim=32,
        d_state=8, groups=1, conv=4, attn_mult=0.0625, emb_mult=12.0,
        res_mult=0.22, eps=1e-5, rank=4, alpha=8.0, n_classes=3)
    S = 32
    weights = reference_lm.init(jax.random.PRNGKey(0), m)
    ad = reference_lm.adapters_init(jax.random.PRNGKey(1), m, 0.01)
    tok = jnp.zeros((1, S), jnp.int32)
    fwd = jax.jit(functools.partial(reference_lm.logits, m=m))
    cost = fwd.lower(weights, ad, tok).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    counted = S * 2 * (flops_lm.forward_macs(m, S)
                       - 3 * m.ssm_heads * m.ssm_head_dim * m.d_state)
    # XLA adds the norms, activations, causal mask and the elementwise
    # part of the recurrence's inputs: within a fifth over the count
    assert counted <= cost["flops"] <= 1.2 * counted + 2 * 64 * 3


def test_lora_count_at_the_published_widths():
    config = run.load_json(run.BENCH, "configs", "granite-4.0-h-micro.json")
    m = reference_lm.dims(config)
    per_token = flops_lm.train_flops_per_token(m, 512)
    # ~3.1 GFLOP a token: 2 x 745M dense MACs, 3 x 8.7M LoRA MACs
    assert 3.0e9 < per_token < 3.2e9
    assert np.isclose(flops_lm.train_flops(m, 512, 10), 10 * per_token)
