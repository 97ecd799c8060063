"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
else is found by name: the cell file ``bench/cells/<cell>.json`` (its
configuration, driver, traffic parameters and limits), the configuration
``bench/configs/<config>.json``, the driver ``bench/drivers/<driver>.py``
and each metric's reader ``bench/metrics/<metric>.py``. Adding a cell,
a configuration or a metric adds files and entries; it edits none.

Set-up (world, weights, the program's own set-up, compilation, and the
checked first units of work) is ``setup_s``. The window then runs whole
units back to back: none starts after ``--seconds``, the one in flight
finishes and counts. With ``--trace 1`` the per-layer metrics are
printed instead of the end-to-end ones: the window runs as without a
trace, for the metrics read on the host clock, and then a second window
of ``--seconds`` runs under the profiler, for those read from the trace.
After the windows the program's state is freed and the plain reference
checks what the program produced in set-up.

Exits 2 without a result when JAX finds no TPU or fewer chips than the
cell needs: no number from another device is ever printed under a
device metric's name.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def cell_spec(name):
    """(benchmark entry, cell file, configuration) of cell ``name``."""
    spec = load_json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in spec["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(BENCH, "cells", f"{name}.json")
    if (cell["config"], cell["traffic"]) != (entry["config"],
                                             entry["traffic"]):
        raise SystemExit(f"{name}: cell file and BENCHMARK.json disagree "
                         "on configuration or traffic")
    return spec, entry, cell, load_json(BENCH, "configs",
                                        f"{entry['config']}.json")


def metrics_for(spec, name, trace):
    """The metric entries this cell reports: end-to-end ones without a
    trace, per-layer ones with it."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def read_metric(metric, run):
    """Value of ``metric`` from its reader ``bench/metrics/<name>.py``;
    None when the reader finds nothing to read."""
    path = os.path.join(BENCH, "metrics", f"{metric['name']}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric["name"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


class CompileClock:
    """Compilations and their seconds, from JAX's monitoring events:
    ``backend`` is XLA compilation (persistent-cache reads included),
    ``trace`` tracing and lowering."""

    def __init__(self, jax):
        self.backend = self.trace = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += duration
            self.count += 1
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace += duration


def devices_or_exit(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"need {chips} TPU chip(s); JAX sees {len(devs)} x "
              f"{devs[0].platform} ({devs[0].device_kind})", file=sys.stderr)
        sys.exit(2)
    return devs


def compile_cache(jax):
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set,
    else at the checkout's fixed ``.jax_cache``; every program cached."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def window(driver, seconds, trace_dir=None):
    """Whole units of work back to back until ``seconds`` have passed.
    Returns (unit records, elapsed seconds)."""
    import jax
    units = []
    if trace_dir:
        # device operations and the benchmark's own host spans only: no
        # Python call tracing, no runtime events on the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = t_unit = time.perf_counter()
            while True:
                units.append(driver.unit())
                elapsed = time.perf_counter() - t0
                units[-1]["wall_s"] = elapsed - (t_unit - t0)
                t_unit = t0 + elapsed
                if elapsed >= seconds:
                    break
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return units, elapsed


def memory_peak(jax, recorders, devs):
    """Bytes at the peak on the fullest chip: every device array alive at
    the windows' close, plus the largest working set (temporaries,
    outputs not aliased to an input, code) of a compiled program that
    the window drove, compiled again from its last call's arguments (a
    hit in the compile cache). Returns (peak, parts)."""
    live = dict.fromkeys([d.id for d in devs], 0)
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            if shard.device.id in live:
                live[shard.device.id] += shard.data.nbytes
    work = {}
    for r in recorders:
        if r.last is None or not hasattr(r.fn, "lower"):
            continue
        args, kwargs = r.last
        ma = r.fn.lower(*args, **kwargs).compile().memory_analysis()
        work[r.attr] = (ma.temp_size_in_bytes + ma.output_size_in_bytes
                        - ma.alias_size_in_bytes
                        + ma.generated_code_size_in_bytes)
    peak = max(live.values()) + max(work.values(), default=0)
    return peak, {"live_bytes": max(live.values()), "program_bytes": work}


def judge(numbers, limits):
    """Each number beside its limit, and whether all are within."""
    checked = {k: {"value": numbers[k], "limit": limits[k]}
               for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checked.values())
    return ok and set(numbers) == set(limits), checked


def execute(spec, name, cell, config, seed, seconds, trace, devs):
    """Set up, run the window, check, and read the metrics: the result
    line's dict. ``devs`` are the devices the cell uses."""
    import jax

    from bench import peaks, trace as tr
    clock = CompileClock(jax)
    driver_mod = importlib.import_module(f"bench.drivers.{cell['driver']}")
    driver = driver_mod.Driver(config, cell["params"], seed)
    setup_s = time.perf_counter() - T_START
    print(f"setup seconds: {setup_s:.1f}, backend compile "
          f"{clock.backend:.1f}, {json.dumps(driver.setup_parts)}, cache "
          f"max size {jax.config.jax_compilation_cache_max_size}",
          file=sys.stderr)

    n_compiles = clock.count
    units, elapsed = window(driver, seconds)
    walls = sorted(u["wall_s"] for u in units)
    print(f"unit seconds: median {walls[len(walls) // 2]:.4f}, "
          f"max {walls[-1]:.4f}", file=sys.stderr)
    traced_units, reduced = None, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            traced_units, traced_s = window(driver, seconds, trace_dir)
            reduced = tr.reduce(tr.load(trace_dir), driver.host_spans)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"traced window: {len(traced_units)} units in "
              f"{traced_s:.3f} s; untraced {len(units)} in {elapsed:.3f} s",
              file=sys.stderr)
    n_compiles = clock.count - n_compiles
    print(f"compiles in windows: {n_compiles}", file=sys.stderr)
    allocator_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devs)
    t_mem = time.perf_counter()
    peak, memory = memory_peak(jax, driver.recorders, devs)
    memory["allocator_peak_bytes"] = allocator_peak
    print(f"memory: {json.dumps(memory)}, "
          f"{time.perf_counter() - t_mem:.1f} s", file=sys.stderr)
    driver.free()
    t_check = time.perf_counter()
    ok, checked = judge(driver.check(), cell["limits"])
    print(f"check seconds: {time.perf_counter() - t_check:.1f}",
          file=sys.stderr)

    kind, on_chip = devs[0].device_kind, devs[0].platform == "tpu"
    run = {"cell": name, "config": config, "params": cell["params"],
           "units": units, "window_s": elapsed, "setup_s": setup_s,
           "traced_units": traced_units, "chips": len(devs),
           "peaks": peaks.chip_peaks(kind) if on_chip else None,
           "trace": reduced, "compiles_in_window": n_compiles}
    metrics = {}
    for m in metrics_for(spec, name, trace):
        value = read_metric(m, run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    # a rehearsal off the chip keeps its readings apart from the metrics
    line = {"correct": ok,
            "attempted": len(units) + len(traced_units or ()), "failed": 0,
            "metrics": metrics if on_chip else {}, "device": device,
            "memory": memory}
    if not on_chip:
        line["rehearsal"] = metrics
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = tr.breakdown(reduced)
    line["checked"] = checked
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, entry, cell, config = cell_spec(args.workload)
    devs = devices_or_exit(entry["chips"])[:entry["chips"]]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    compile_cache(jax)
    line = execute(spec, args.workload, cell, config, args.seed,
                   args.seconds, args.trace, devs)
    for k, v in line["checked"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
