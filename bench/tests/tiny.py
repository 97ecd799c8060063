"""A cell cut to a size a CPU test can hold: 10 devices, 3 edges, 6
scheduled, 20-40 samples each, L=Q=2."""
from bench import run


def cell(name):
    """(benchmark spec, cell file, configuration) of ``name``, cut."""
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.load_json(run.BENCH, "cells", f"{name}.json")
    config = run.load_json(run.BENCH, "configs", f"{cell['config']}.json")
    config["system"].update(n_devices=10, n_edges=3, d_range=[20, 40],
                            L=2, Q=2)
    config["data"].update(n_train=300, n_test=100)
    config["H"] = 6
    cell["params"].update(check_rounds=2, block=3, horizon_s=3000.0)
    return spec, cell, config
