"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke|--perf] [--only NAME]

Emits ``name,us_per_call,derived`` CSV lines (stdout). Heavy suites run at
reduced scale by default (CPU container); EXPERIMENTS.md records the
scale factors and validates the paper's *relative* claims. ``--smoke``
restricts to the perf-tracking micro-benchmarks (engine / hfel /
hier_agg / drl_train / sweep_shard / sweep_fused / schedule_scale /
async_engine / comm_compress / model_zoo) at their tiny CI shapes — the
bench-smoke CI job runs exactly that and uploads the ``results/*.json``
outputs as artifacts.
``--perf`` runs the same ten at full scale but writes the JSON under
``results/`` (gitignored), so the weekly CI job's artifacts are always
freshly produced files, never the committed repo-root ``BENCH_*.json``.
``--check`` then compares the fresh smoke timings against the committed
``benchmarks/baselines/*.json`` and fails the run on a >2x slowdown of
any shared ``*_ms`` field (``$BENCH_CHECK_FACTOR`` overrides the
factor; the 5ms noise floor applies per field — sub-floor baselines are
gated against ``floor*factor`` rather than skipped). The full guard
contract is documented in ``benchmarks/README.md``.

Each sub-benchmark runs in its own try block: one failure prints a
``<name>,0.0,FAILED`` line and the remaining suites still run, but the
process exits non-zero so CI can gate on the harness. Per-suite wall
times are collected and, when ``$GITHUB_STEP_SUMMARY`` is set (any
GitHub Actions job), appended there as a markdown table.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import traceback

BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "baselines")


def _perf_fields(obj, prefix=""):
    """Recursively collect comparable perf entries from a bench JSON.

    Returns {path: (value_ms_or_rate, kind)} with kind "time" for
    ``*_ms`` / ``*_s`` fields (normalised to ms; lower is better) and
    "rate" for ``*_per_s`` throughputs (higher is better). Walks nested
    dicts AND lists so every smoke baseline contributes fields (hfel
    emits ``*_s`` under cases, drl only ``*_eps_per_s``, hier_agg a list
    of sweep rows)."""
    out = {}
    if isinstance(obj, dict):
        items = obj.items()
    elif isinstance(obj, list):
        items = ((str(i), v) for i, v in enumerate(obj))
    else:
        return out
    for k, v in items:
        path = f"{prefix}{k}"
        if isinstance(v, (dict, list)):
            out.update(_perf_fields(v, prefix=f"{path}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            if k.endswith("_per_s"):
                out[path] = (float(v), "rate")
            elif k.endswith("_ms"):
                out[path] = (float(v), "time")
            elif k.endswith("_s"):
                out[path] = (float(v) * 1e3, "time")
    return out


def check_regressions(results_dir: str = "results",
                      baseline_dir: str = BASELINE_DIR,
                      factor: float | None = None,
                      floor_ms: float = 5.0) -> list[str]:
    """Compare fresh smoke perf numbers against the committed baselines.

    For every baseline under ``benchmarks/baselines/``, the matching
    fresh file under ``results_dir`` must exist (a missing file means
    the results pipeline drifted — that IS a failure, not a skip) and
    each shared field must stay within ``factor``x of the baseline
    (default 2, override via $BENCH_CHECK_FACTOR): timing fields
    (``*_ms`` / ``*_s``) must not slow down past factor*x, throughput
    fields (``*_per_s``) must not drop below baseline/factor. The noise
    floor applies PER FIELD: a timing field is gated against
    ``max(baseline, floor_ms) * factor``, so sub-5ms baselines (pure
    dispatch overhead at smoke shapes) tolerate jitter up to
    ``floor_ms * factor`` but still fail on a real blow-up — the old
    behaviour of skipping them entirely let a 4ms -> 400ms regression
    through unreported. Comparing zero fields overall is also a failure
    (a vacuously green guard is a disabled guard). Returns the list of
    violation strings. Full contract: ``benchmarks/README.md``.
    """
    if factor is None:
        factor = float(os.environ.get("BENCH_CHECK_FACTOR", "2.0"))
    failures = []
    compared = 0
    for base_path in sorted(glob.glob(os.path.join(baseline_dir,
                                                   "BENCH_*.json"))):
        name = os.path.basename(base_path)
        fresh_path = os.path.join(results_dir, name)
        if not os.path.exists(fresh_path):
            failures.append(f"{name}: fresh results file missing under "
                            f"{results_dir}/ (pipeline drift?)")
            continue
        with open(base_path) as fh:
            base = _perf_fields(json.load(fh))
        with open(fresh_path) as fh:
            fresh = _perf_fields(json.load(fh))
        for field, (base_v, kind) in sorted(base.items()):
            if field not in fresh or fresh[field][1] != kind:
                continue
            if kind == "rate" and base_v <= 0:
                continue
            compared += 1
            fresh_v = fresh[field][0]
            # per-field noise floor: sub-floor baselines are measured
            # against floor_ms*factor instead of being skipped, so
            # dispatch-overhead jitter passes but a real blow-up fails
            if kind == "time" and fresh_v > max(base_v, floor_ms) * factor:
                failures.append(
                    f"{name}:{field} {fresh_v:.1f}ms vs baseline "
                    f"{base_v:.1f}ms ({fresh_v / max(base_v, 1e-9):.2f}x, "
                    f"gate {max(base_v, floor_ms) * factor:.1f}ms)")
            elif kind == "rate" and fresh_v < base_v / factor:
                failures.append(
                    f"{name}:{field} {fresh_v:.2f}/s vs baseline "
                    f"{base_v:.2f}/s ({base_v / fresh_v:.2f}x drop > "
                    f"{factor:.1f}x)")
    if compared == 0:
        failures.append("no comparable fields between baselines and "
                        "fresh results — guard is vacuous")
    status = f"failures={len(failures)}" if failures else "ok"
    print(f"bench-check,{compared:.1f},{status}", flush=True)
    for f in failures:
        print(f"bench-check-REGRESSION,0.0,{f}", flush=True)
    return failures


def write_step_summary(rows, total_s: float, path: str | None = None) -> None:
    """Append the per-suite timings table to $GITHUB_STEP_SUMMARY (no-op
    outside GitHub Actions). rows: [(suite, seconds, status)]."""
    path = path or os.environ.get("GITHUB_STEP_SUMMARY")
    if not path:
        return
    lines = ["## Benchmark suite timings", "",
             "| suite | wall time | status |",
             "|---|---:|---|"]
    for name, secs, status in rows:
        lines.append(f"| {name} | {secs:.1f} s | {status} |")
    lines += [f"| **total** | **{total_s:.1f} s** | |", ""]
    with open(path, "a") as fh:
        fh.write("\n".join(lines) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="table2|fig34|fig5|fig6|fig7|"
                         "engine|hfel|hier_agg|drl_train|sweep_shard|"
                         "sweep_fused|schedule_scale|async_engine|"
                         "comm_compress|model_zoo")
    ap.add_argument("--fast", action="store_true",
                    help="minimal iteration counts")
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: only the perf micro-benchmarks at "
                         "tiny shapes (JSON under results/)")
    ap.add_argument("--perf", action="store_true",
                    help="only the perf micro-benchmarks at full scale, "
                         "JSON written under results/ (fresh files for "
                         "CI artifacts — never the committed repo-root "
                         "BENCH_*.json)")
    ap.add_argument("--check", action="store_true",
                    help="after the suites, compare results/*_smoke.json "
                         "timings against the committed "
                         "benchmarks/baselines/ and exit non-zero on a "
                         ">2x slowdown ($BENCH_CHECK_FACTOR overrides)")
    args = ap.parse_args()
    from repro.utils import enable_compile_cache
    enable_compile_cache()

    state = {"trained": None}

    def run_table2():
        from benchmarks import table2_clustering
        table2_clustering.run()

    def run_fig5():
        from benchmarks import fig5_drl_curve
        state["trained"] = fig5_drl_curve.run(
            episodes=80 if args.fast else 400)

    def run_fig6():
        from benchmarks import fig6_assignment
        fig6_assignment.run(trained_trainer=state["trained"],
                            n_pops=4 if args.fast else 12)

    def run_fig34():
        from benchmarks import fig34_convergence
        fig34_convergence.run(iters=4 if args.fast else 10,
                              h_values=(10,) if args.fast else (10, 20))

    def run_fig7():
        from benchmarks import fig7_framework
        fig7_framework.run(h_values=(10, 20) if args.fast else (10, 20, 40),
                           max_iters=4 if args.fast else 12)

    def _perf_bench(mod, name):
        if args.smoke:
            mod.run_smoke()
        elif args.perf:
            mod.run(out_json=f"results/BENCH_{name}.json")
        else:
            mod.run()

    def run_engine():
        from benchmarks import bench_round_engine
        _perf_bench(bench_round_engine, "round_engine")

    def run_hfel():
        from benchmarks import bench_hfel_search
        _perf_bench(bench_hfel_search, "hfel_search")

    def run_hier_agg():
        from benchmarks import bench_hier_agg
        _perf_bench(bench_hier_agg, "hier_agg")

    def run_drl_train():
        from benchmarks import bench_drl_train
        _perf_bench(bench_drl_train, "drl_train")

    def run_sweep_shard():
        from benchmarks import bench_sweep_shard
        _perf_bench(bench_sweep_shard, "sweep_shard")

    def run_sweep_fused():
        from benchmarks import bench_sweep_fused
        _perf_bench(bench_sweep_fused, "sweep_fused")

    def run_schedule_scale():
        from benchmarks import bench_schedule_scale
        _perf_bench(bench_schedule_scale, "schedule_scale")

    def run_async_engine():
        from benchmarks import bench_async_engine
        _perf_bench(bench_async_engine, "async_engine")

    def run_comm_compress():
        from benchmarks import bench_comm_compress
        _perf_bench(bench_comm_compress, "comm_compress")

    def run_model_zoo():
        from benchmarks import bench_model_zoo
        _perf_bench(bench_model_zoo, "model_zoo")

    # fig6 reuses fig5's trained D3QN when both are selected, so order
    # matters: fig5 before fig6
    suites = [
        ("table2", run_table2),
        ("fig5", run_fig5),
        ("fig6", run_fig6),
        ("fig34", run_fig34),
        ("fig7", run_fig7),
        ("engine", run_engine),
        ("hfel", run_hfel),
        ("hier_agg", run_hier_agg),
        ("drl_train", run_drl_train),
        ("sweep_shard", run_sweep_shard),
        ("sweep_fused", run_sweep_fused),
        ("schedule_scale", run_schedule_scale),
        ("async_engine", run_async_engine),
        ("comm_compress", run_comm_compress),
        ("model_zoo", run_model_zoo),
    ]
    if args.smoke or args.perf:
        perf_names = ("engine", "hfel", "hier_agg", "drl_train",
                      "sweep_shard", "sweep_fused", "schedule_scale",
                      "async_engine", "comm_compress", "model_zoo")
        suites = [(n, fn) for n, fn in suites if n in perf_names]

    names = [n for n, _ in suites]
    if args.only is not None and args.only not in names:
        ap.error(f"--only must be one of {'|'.join(names)}")

    print("name,us_per_call,derived", flush=True)
    t_all = time.time()
    failed = []
    timings = []
    for name, fn in suites:
        if args.only not in (None, name):
            continue
        t0 = time.time()
        try:
            fn()
            timings.append((name, time.time() - t0, "ok"))
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name},0.0,FAILED", flush=True)
            failed.append(name)
            timings.append((name, time.time() - t0, "FAILED"))
    # the regression check runs BEFORE the status line / step summary so
    # a check-only failure is visible in both, not just the exit code
    if args.check:
        t0 = time.time()
        regressions = check_regressions()
        if regressions:
            failed.append("bench-check")
        timings.append(("bench-check", time.time() - t0,
                        "FAILED" if regressions else "ok"))
    total = time.time() - t_all
    status = f"failed={'|'.join(failed)}" if failed else "ok"
    print(f"benchmark_suite_total,{total * 1e6:.0f},{status}", flush=True)
    write_step_summary(timings, total)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
