"""Arithmetic on the client's operation records: the yardstick for every
client-side number. An op is a dict with at least ``stream``, ``kind``,
``bytes``, ``t_due``, ``t_start``, ``t_end`` (CLOCK_MONOTONIC seconds, shared by
the generator process and the harness) and ``ok``."""

from __future__ import annotations

import math


def in_window(ops: list[dict], t0: float, t1: float, kind: str | None = None) -> list[dict]:
    """Ops that both start and finish inside [t0, t1] (edge ops are excluded)."""
    return [o for o in ops
            if o["t_start"] >= t0 and o["t_end"] <= t1
            and (kind is None or o["kind"] == kind)]


def c2c_bytes_per_s(ops: list[dict], t0: float, t1: float, kind: str) -> float | None:
    """Completion-to-completion throughput, summed over streams.

    Per stream: bytes of the ok ops that start and finish inside the window,
    over (last finish - first start) of those ops. A stall inside a stream's
    span is counted in its time; the partial objects at both window edges are
    left out of bytes and time alike, so the edges neither add nor lose work.
    A failed op keeps its time in the span and contributes no bytes."""
    total = 0.0
    seen = False
    streams: dict[int, list[dict]] = {}
    for o in in_window(ops, t0, t1, kind):
        streams.setdefault(o["stream"], []).append(o)
    for sops in streams.values():
        span = max(o["t_end"] for o in sops) - min(o["t_start"] for o in sops)
        if span <= 0:
            continue
        total += sum(o["bytes"] for o in sops if o["ok"]) / span
        seen = True
    return total if seen else None


def fixed_window_bytes_per_s(ops: list[dict], t0: float, t1: float, kind: str) -> float:
    """The plain accounting: bytes of ok ops acknowledged inside [t0, t1] over
    its length, wherever they started. Kept beside c2c for comparison."""
    got = sum(o["bytes"] for o in ops
              if o["ok"] and o["kind"] == kind and t0 <= o["t_end"] <= t1)
    return got / (t1 - t0)


def percentile(values: list[float], q: float) -> float | None:
    """Linear-interpolated percentile (q in 0..100) of raw samples."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def due_latencies_ms(ops: list[dict], t0: float, t1: float, kind: str | None = None) -> list[float]:
    """Latency of every op DUE inside the window, timed from when it was due
    to be sent (so a stall charges the requests queued behind it), in ms."""
    return [(o["t_end"] - o["t_due"]) * 1e3 for o in ops
            if t0 <= o["t_due"] <= t1 and (kind is None or o["kind"] == kind)]


def timeline(ops: list[dict], t0: float, t1: float, step: float = 1.0) -> list[int]:
    """Acknowledged bytes per ``step`` seconds of the window, by finish time."""
    n = max(1, math.ceil((t1 - t0) / step))
    out = [0] * n
    for o in ops:
        if o["ok"] and t0 <= o["t_end"] < t1:
            out[min(n - 1, int((o["t_end"] - t0) / step))] += o["bytes"]
    return out
