"""§Roofline: three-term roofline per (arch x shape) from the dry-run.

  compute term    = HLO_FLOPs / peak bf16 FLOP/s
  memory term     = HLO_bytes / peak HBM B/s
  collective term = collective_bytes / peak ICI B/s per link

Peaks come from ``launch.mesh.chip_peaks(DEVICE_KIND)``: the v5e the
dry-run meshes describe (a kind without published peaks raises).

HLO_FLOPs/bytes/collective_bytes are the probe-corrected per-device
totals from results/probes.json (the raw dryrun.json numbers undercount
scan bodies); chips divide out because our sources are already
per-device. MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*tokens
(decode) gives the useful-compute ratio.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

from benchmarks.common import emit
from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get_config, variant_for_shape
from repro.launch.mesh import chip_peaks

CHIPS = 256
DEVICE_KIND = "TPU v5 lite"


def model_flops(arch: str, shape_name: str) -> float:
    """Useful FLOPs per step (whole job, not per device)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = variant_for_shape(get_config(arch), shape)
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def roofline_terms(rec: Dict, probe: Optional[Dict]) -> Dict:
    """rec: dryrun.json record; probe: probes.json record (or None)."""
    peaks = chip_peaks(DEVICE_KIND)
    if probe and "flops" in probe:
        flops_dev = probe["flops"]
        bytes_dev = probe["bytes"]
        coll_dev = probe["coll"]
        src = "probe"
    else:
        flops_dev = rec["cost"].get("flops", 0.0)
        bytes_dev = rec["cost"].get("bytes accessed", 0.0)
        coll_dev = sum(v["bytes"] for v in rec["collectives"].values())
        src = "raw(scan-undercounted)"
    t_comp = flops_dev / peaks["flops_bf16"]
    t_mem = bytes_dev / peaks["hbm_bw"]
    t_coll = coll_dev / peaks["ici_bw_per_link"]
    dominant = max(("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / (flops_dev * CHIPS) if flops_dev else 0.0
    return {"t_compute_s": t_comp, "t_memory_s": t_mem,
            "t_collective_s": t_coll, "dominant": dominant,
            "model_flops": mf, "useful_ratio": useful, "source": src}


RECOMMEND = {
    "compute": "reduce recompute (remat policy) / raise MoE capacity "
               "utilisation; compute term is floor-bound by model FLOPs",
    "memory": "fuse/bf16-ify the biggest HBM streams (weights are "
              "re-read per microbatch: fewer, larger microbatches)",
    "collective": "overlap collectives with compute; move the dominant "
                  "all-gather to the smaller mesh axis or shard the "
                  "producing tensor differently",
}


def run(dryrun_path="results/dryrun.json", probes_path="results/probes.json",
        out_path="results/roofline.json", mesh="16x16"):
    if not os.path.exists(dryrun_path):
        emit("roofline/missing", 0.0, f"no {dryrun_path}; run dryrun first")
        return
    with open(dryrun_path) as f:
        recs = [r for r in json.load(f) if r.get("mesh") == mesh
                and "error" not in r]
    probes = {}
    if os.path.exists(probes_path):
        with open(probes_path) as f:
            probes = {(p["arch"], p["shape"]): p for p in json.load(f)
                      if "error" not in p}
    table = []
    for r in recs:
        t = roofline_terms(r, probes.get((r["arch"], r["shape"])))
        t.update(arch=r["arch"], shape=r["shape"],
                 temp_gb=r["memory"]["temp_bytes"] / 1e9,
                 args_gb=r["memory"]["argument_bytes"] / 1e9,
                 fits_16g=(r["memory"]["temp_bytes"]
                           + r["memory"]["argument_bytes"]) < 16e9,
                 recommend=RECOMMEND[t["dominant"]])
        table.append(t)
        emit(f"roofline/{r['arch']}/{r['shape']}", 0.0,
             f"comp={t['t_compute_s']:.4f}s;mem={t['t_memory_s']:.4f}s;"
             f"coll={t['t_collective_s']:.4f}s;dom={t['dominant']};"
             f"useful={t['useful_ratio']:.2f};src={t['source']}")
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(table, f, indent=1)
    n_dom = {}
    for t in table:
        n_dom[t["dominant"]] = n_dom.get(t["dominant"], 0) + 1
    emit("roofline/summary", 0.0,
         f"pairs={len(table)};dominants={n_dom}")


if __name__ == "__main__":
    run()
