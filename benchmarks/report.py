"""Generate EXPERIMENTS.md tables from results/*.json.

    PYTHONPATH=src python -m benchmarks.report > EXPERIMENTS_tables.md
"""
from __future__ import annotations

import json
import os


def load(path):
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None


def dryrun_table(recs, mesh):
    rows = [r for r in recs if r.get("mesh") == mesh]
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    rows.sort(key=lambda r: (r["arch"], order.get(r["shape"], 9)))
    out = [f"### Mesh {mesh}",
           "",
           "| arch | shape | status | compile s | HLO flops/dev (raw) | "
           "temp GB/dev | args GB/dev | AG GB | AR GB | A2A GB |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "error" in r:
            out.append(f"| {r['arch']} | {r['shape']} | **ERROR** "
                       f"{r['error'][:60]} | | | | | | | |")
            continue
        c = r["collectives"]
        out.append(
            f"| {r['arch']} | {r['shape']} | ok | {r['compile_s']:.0f} | "
            f"{r['cost'].get('flops', 0):.2e} | "
            f"{r['memory']['temp_bytes']/1e9:.1f} | "
            f"{r['memory']['argument_bytes']/1e9:.2f} | "
            f"{c['all-gather']['bytes']/1e9:.1f} | "
            f"{c['all-reduce']['bytes']/1e9:.1f} | "
            f"{c['all-to-all']['bytes']/1e9:.2f} |")
    return "\n".join(out)


def main():
    dr = load("results/dryrun.json") or []
    print("<!-- generated by benchmarks/report.py -->\n")
    print("## Dry-run records\n")
    print(dryrun_table(dr, "16x16"))
    print()
    print(dryrun_table(dr, "2x16x16"))


if __name__ == "__main__":
    main()
