"""From a profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` file that ``jax.profiler`` writes into
plain event tuples; ``reduce`` works on those alone, so that a small
recorded trace checks it (``bench/tests``). Device planes are those
named ``/device:TPU:<n>``. On each, an operation runs in an event of
the "XLA Ops" line, and a whole program in an event of the "XLA
Modules" line, named after its jitted function (``jit_round_step(12)``).
Host spans are the benchmark's own ``TraceAnnotation`` names.
"""
from __future__ import annotations

import collections
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def load(trace_dir):
    """Events of the newest trace under ``trace_dir``:
    a list of (plane, line, name, start_ns, duration_ns)."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    return [(plane.name, line.name, ev.name, ev.start_ns, ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def _union(intervals):
    """Merged, sorted, disjoint (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def program_name(module_event_name):
    """``jit_round_step(123)`` -> ``round_step``."""
    return MODULE_NAME.match(module_event_name).group(1)


def reduce(events, host_spans=()):
    """Busy time, per-program device time and idle gaps of the window.

    The window is the host span ``bench.window``. For every device plane:
    busy is the union of its operations' intervals inside the window
    (program intervals where a plane has no operation line), and each
    program's time is the sum of its module events inside the window.
    Each idle gap is named after the host span in ``host_spans`` that
    overlaps it most ("host" where none does). Returns a dict, or None
    when the trace holds no device plane with an event in the window.
    """
    win = [(s, s + d) for _, _, name, s, d in events if name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win[0]
    ops, modules = collections.defaultdict(list), collections.defaultdict(
        list)
    spans = []
    for plane, line, name, s, d in events:
        if DEVICE_PLANE.match(plane):
            if line == OPS_LINE:
                ops[plane].append((s, s + d))
            elif line == MODULES_LINE:
                modules[plane].append((name, s, s + d))
        elif name in host_spans:
            spans.append((name, s, s + d))
    planes = sorted(set(ops) | set(modules))
    busy, programs, gaps = {}, collections.Counter(), []
    for plane in planes:
        intervals = ops[plane] or [(s, e) for _, s, e in modules[plane]]
        merged = _union(_clip(intervals, lo, hi))
        busy[plane] = sum(e - s for s, e in merged) * 1e-9
        for name, s, e in modules[plane]:
            for cs, ce in _clip([(s, e)], lo, hi):
                programs[program_name(name)] += (ce - cs) * 1e-9
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((_doing(spans, gs, ge), (ge - gs) * 1e-9))
    if not any(busy.values()):
        return None
    n = len(planes)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy.values()) / n,
        "busy_s_per_chip": [busy[p] for p in planes],
        "chips": n,
        # summed over chips: divide by ``chips`` for a per-chip time
        "program_s": dict(programs),
        "idle_gaps": sorted(gaps, key=lambda g: -g[1]),
    }


def _doing(spans, s, e):
    """The host span that overlaps [s, e) the most."""
    best, name = 0, "host"
    for n, ss, se in spans:
        ov = min(e, se) - max(s, ss)
        if ov > best:
            best, name = ov, n
    return name


def breakdown(reduced, top=10):
    """The ``breakdown`` of a result line: the programs that took the
    most device time (per chip) and the longest idle gaps."""
    per_chip = {k: v / reduced["chips"]
                for k, v in reduced["program_s"].items()}
    return {
        "device_ops": [[k, v] for k, v in sorted(
            per_chip.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"][:top]],
    }
