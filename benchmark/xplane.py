"""Reduction of a profiler trace (.xplane.pb) to device intervals.

What a TPU v5e trace holds (looked at by hand, PR 24): one plane per chip named
"/device:TPU:<n>" whose line "XLA Ops" has one event per HLO op run on the
chip, named by its HLO text; a plane "/host:CPU" whose thread lines hold
jax.profiler.TraceAnnotation spans (the benchmark's are named "bench:..."). All
on one clock, in ns from the moment start_trace was called."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_trace(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def load(path: str) -> dict:
    """-> {"devices": {plane: [(name, start_s, end_s), ...]},
           "annotations": [(name, start_s, end_s), ...]}"""
    from jax.profiler import ProfileData

    devices: dict[str, list] = {}
    annotations: list = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                annotations.extend(
                    (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith("bench:"))
    return {"devices": devices, "annotations": annotations}


def clip(events: list, lo: float, hi: float) -> list:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def union(events: list) -> list[tuple[float, float]]:
    """Merged [start, end] intervals of the events, in time order."""
    out: list[list[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events: list) -> float:
    return sum(e - s for s, e in union(events))


def gaps(events: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of [lo, hi] in which no event runs, longest first."""
    out, at = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def window_of(trace: dict, name: str) -> tuple[float, float] | None:
    """[start, end] of the benchmark's own annotation ``name``."""
    for n, s, e in trace["annotations"]:
        if n == name:
            return s, e
    return None


def attribute(gap: tuple[float, float], trace: dict, skip: str) -> str:
    """What the host was doing at the middle of a gap, as far as the
    benchmark's own annotations say; the daemon has no spans on this clock."""
    mid = (gap[0] + gap[1]) / 2
    inner = [(e - s, n) for n, s, e in trace["annotations"] if s <= mid <= e and n != skip]
    return "host:" + min(inner)[1] if inner else "host:_unattributed"


def op_label(name: str) -> str:
    """An HLO op's text cut to what tells programs apart (the op, its result
    shape and, for the kernel, its matrix shape), in name characters."""
    flat = re.sub(r"\{[^}]*\}", "", name)
    head = flat.split("(", 1)[0].rsplit(" ", 1)[0] if "(" in flat else flat
    m = re.search(r"custom-call\((s8\[[\d,]+\])", flat)
    label = head + (" " + m.group(1) if m else "")
    return re.sub(r"[^A-Za-z0-9.\-]+", "_", label).strip("_")[:96]


def top_ops(events: list, n: int = 10) -> list[list]:
    total: dict[str, float] = {}
    for name, s, e in events:
        key = op_label(name)
        total[key] = total.get(key, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def device_summary(trace: dict, window: str) -> dict:
    """busy_s (averaged over chips), window_s, and the breakdown lists."""
    lo, hi = window_of(trace, window)
    per_chip = [clip(evs, lo, hi) for evs in trace["devices"].values()]
    busy = sum(busy_seconds(evs) for evs in per_chip) / max(1, len(per_chip))
    every = [e for evs in per_chip for e in evs]
    longest = gaps(every, lo, hi)[:10]
    return {"device": {"busy_s": busy, "window_s": hi - lo},
            "breakdown": {"device_ops": top_ops(every),
                          "idle_gaps": [[attribute(g, trace, window), g[1] - g[0]] for g in longest]}}
