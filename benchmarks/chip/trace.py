"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reduces a JAX profiler trace (``*.xplane.pb``) to plain lists:
the device's operations and its executables ("XLA Ops" and "XLA Modules"
lines of the chip's plane), and the host annotations the harness opened
(``jax.profiler.TraceAnnotation``), all on the profiler's one clock.  The
reduction below works on those lists alone, so a recorded trace can be
kept as a small JSON fixture and checked on any machine.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

# host annotations the harness opens; an idle gap on the device is charged
# to the one that was open over most of it
HOST_SPANS = ("engine.step", "client.submit", "client.wait")
WINDOW_SPAN = "bench.window"


def load(logdir: str, device_index: int = 0) -> dict:
    """Reduce the trace under ``logdir`` to ``{"ops", "modules", "host",
    "window"}``: ``[name, start_ns, dur_ns]`` lists and the traced window
    ``[start_ns, end_ns]`` (the harness's ``bench.window`` annotation)."""
    import jax

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = {"ops": [], "modules": [], "host": [], "window": None}
    device_plane = f"/device:TPU:{device_index}"
    for plane in data.planes:
        if plane.name == device_plane:
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    out[key] += [[op_name(e.name), e.start_ns, e.duration_ns]
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out["host"].append([e.name, e.start_ns, e.duration_ns])
                    elif e.name == WINDOW_SPAN:
                        out["window"] = [e.start_ns, e.start_ns + e.duration_ns]
    if out["window"] is None or not out["ops"]:
        raise RuntimeError(f"trace {paths[0]} has no {WINDOW_SPAN} span or "
                           f"no operation on {device_plane}")
    for key in ("ops", "modules", "host"):
        out[key].sort(key=lambda e: e[1])
    return out


def op_name(raw: str) -> str:
    """An operation's name without its HLO text: ``%qmm_bitserial_4b.87 =
    f32[...] custom-call(...)`` is ``qmm_bitserial_4b.87``."""
    return raw[1:].split(" ", 1)[0] if raw.startswith("%") else raw


def kind(name: str) -> str:
    """An operation's name without the instance number XLA appends:
    ``qmm_bitserial_4b.87`` is ``qmm_bitserial_4b``."""
    base, dot, num = name.rpartition(".")
    return base if dot and num.isdigit() else name


def _clip(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def busy_intervals(trace) -> list[tuple[float, float]]:
    """The union of the device's operation intervals inside the window."""
    lo, hi = trace["window"]
    merged: list[list[float]] = []
    for _, s, e in sorted(_clip(trace["ops"], lo, hi), key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def window_s(trace) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def busy_s(trace) -> float:
    return sum(e - s for s, e in busy_intervals(trace)) / 1e9


def idle_gaps(trace, top: int = 10) -> list[tuple[str, float]]:
    """The longest stretches of the window with no operation on the device,
    each named by the host annotation that was open over most of it
    (``none`` when the harness had none open)."""
    lo, hi = trace["window"]
    edges = [lo] + [x for iv in busy_intervals(trace) for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = trace["host"]
    out = []
    for s, e in gaps[:top]:
        cover = defaultdict(float)
        for name, hs, he in _clip(host, s, e):
            cover[name] += he - hs
        label = max(cover, key=cover.get) if cover else "none"
        out.append((label, (e - s) / 1e9))
    return out


def modules(trace, name: str) -> list[tuple[float, float]]:
    """``(start_ns, end_ns)`` of each execution, inside the window, of the
    executable that jit names ``jit_<name>``."""
    lo, hi = trace["window"]
    want = f"jit_{name}"
    return [(s, e) for n, s, e in _clip(trace["modules"], lo, hi)
            if n == want or n.startswith(want + "(") or n.startswith(want + ".")]


def ops_within(trace, spans, prefixes) -> float:
    """Seconds of the operations whose names start with one of ``prefixes``
    and that run inside one of ``spans`` (executions of one executable)."""
    total = 0.0
    spans = sorted(spans)
    starts = [s for s, _ in spans]
    for name, s, e in _clip(trace["ops"], *trace["window"]):
        if not name.startswith(prefixes):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            total += e - s
    return total / 1e9


def top_ops(trace, top: int = 10) -> list[tuple[str, float]]:
    """The device operations that took most time in the window, each kind
    summed over its instances (the 8 layers' ``qmm_bitserial_4b.*`` are
    one entry)."""
    tot = defaultdict(float)
    for name, s, e in _clip(trace["ops"], *trace["window"]):
        tot[kind(name)] += (e - s) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]
