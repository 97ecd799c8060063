"""Launch entry points: the streaming serve CLI's checkpoint/eval
cadence (through the importable ``run_serve`` core) and the pure
HLO-parsing helpers of ``launch/dryrun.py`` (ISSUE 8 satellite —
previously untested entry points)."""
import json
import os

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.checkpoint import ckpt  # noqa: E402
from repro.launch.dryrun import _shape_bytes, parse_collectives  # noqa: E402
from repro.launch.serve import build_trace, run_serve  # noqa: E402

SMOKE = dict(n_devices=10, n_edges=3, H=6, n_train=300, n_test=120,
             alloc_steps=40, L=2, Q=3, seed=0)


def test_run_serve_checkpoint_and_eval_cadence(tmp_path):
    """4 streamed rounds, eval every 2, checkpoint every 2: JSON lines
    carry accuracy exactly on eval rounds; step dirs land on ckpt
    rounds; the summary counts both."""
    lines = []
    out = tmp_path / "summary.json"
    summary = run_serve(rounds=4, eval_every=2, ckpt_every=2,
                        ckpt_dir=str(tmp_path / "ck"),
                        out_json=str(out), log=lines.append, **SMOKE)

    recs = [json.loads(ln) for ln in lines]
    assert [r["round"] for r in recs] == [1, 2, 3, 4]
    assert [r["acc"] is not None for r in recs] == [False, True,
                                                   False, True]
    assert all(r["t"] > 0 for r in recs)

    assert summary["n_checkpoints"] == 2
    steps = sorted(os.listdir(tmp_path / "ck"))
    assert steps == ["step_00000002", "step_00000004"]
    assert ckpt.latest_step(str(tmp_path / "ck")) == 4

    saved = json.loads(out.read_text())
    assert saved["rounds"] == 4
    assert saved["final_acc"] == pytest.approx(recs[-1]["acc"])


def test_run_serve_restores_checkpointed_params(tmp_path):
    """The streamed checkpoints round-trip through restore_pytree."""
    from repro.core.async_engine import AsyncConfig, AsyncHFLEngine
    from repro.launch.serve import build_world
    run_serve(rounds=2, eval_every=0, ckpt_every=2,
              ckpt_dir=str(tmp_path), log=lambda _: None, **SMOKE)
    sp, pop, fed = build_world(10, 3, 300, 120, 0, L=2, Q=3)
    template = AsyncHFLEngine(sp, pop, fed, AsyncConfig(H=6)).model_params
    restored = ckpt.restore_pytree(template, str(tmp_path))
    import jax
    for leaf in jax.tree.leaves(restored):
        assert np.isfinite(np.asarray(leaf)).all()


def test_build_trace_presets():
    for name in ("always-on", "stationary", "diurnal", "bursty"):
        tr = build_trace(name, 8, seed=0)
        assert tr.n_devices == 8
        assert tr.latency_scale.shape == (8,)
    with pytest.raises(ValueError):
        build_trace("nope", 8, seed=0)
    assert build_trace("always-on", 8, seed=0).init_up.all()


def test_build_world_prices_and_partitions_one_data_size_range():
    """The cost model's D_n and the partitioned data come from the same
    range (they used to be priced at 50-90 but partitioned at 20-40)."""
    from repro.launch.serve import build_world
    sp, pop, fed = build_world(10, 3, 300, 120, 0, d_range=(15, 25))
    assert sp.d_range == (15, 25)
    D = np.asarray(pop.D)
    assert D.min() >= 15 and D.max() <= 25
    assert min(fed.sizes) >= 15 and max(fed.sizes) <= 25


def test_chip_peaks_keyed_by_device_kind():
    from repro.launch.mesh import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(ValueError):
        chip_peaks("cpu")


def test_compile_cache_respects_env_else_repo_dir(monkeypatch):
    import jax
    from repro.utils import REPO_ROOT, enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_smoke_refuses_the_cpu():
    """The chip smoke never falls back to the CPU: it exits 2 at the
    device check and prints no result line."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "device check failed" in proc.stderr


# ------------------------------------------------------------- dryrun

def test_shape_bytes_parses_dtype_and_dims():
    assert _shape_bytes("bf16[16,512,1024]") == 16 * 512 * 1024 * 2
    assert _shape_bytes("f32[8,4]") == 8 * 4 * 4
    assert _shape_bytes("f32[]") == 4          # scalar
    assert _shape_bytes("not a shape") == 0


def test_parse_collectives_counts_ops_and_bytes():
    hlo = """
      ENTRY %main {
        %p0 = f32[8,4]{1,0} parameter(0)
        %ag = f32[16,4]{1,0} all-gather(%p0), replica_groups={{0,1}}
        %ar = f32[8,4]{1,0} all-reduce(%p0), to_apply=%add
        %mul = f32[8,4]{1,0} multiply(%p0, %p0)
      }
    """
    out = parse_collectives(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 16 * 4 * 4
    assert out["all-reduce"]["count"] == 1
    assert out["all-to-all"]["count"] == 0
