"""Small readings the reducers share: counter deltas over the window, user
bytes between the two snapshots, the kernel's events in the traced window."""

from __future__ import annotations

import kernelmodel
import xplane


def delta(ctx: dict, names: list[str]) -> float:
    a, b = ctx["snap0"]["counters"], ctx["snap1"]["counters"]
    return sum(b.get(n, 0.0) - a.get(n, 0.0) for n in names)


def user_bytes(ctx: dict, kind: str) -> int:
    """Bytes of the ok ops of ``kind`` acknowledged between the snapshots."""
    lo, hi = ctx["snap0"]["t"], ctx["snap1"]["t"]
    return sum(o["bytes"] for o in ctx["ops"]
               if o["ok"] and o["kind"] == kind and lo <= o["t_end"] <= hi)


def kernel_events(trace: dict) -> tuple[list[tuple[dict, float]], float]:
    """-> ([(call shapes, device seconds)], traced window seconds)."""
    lo, hi = xplane.window_of(trace, "bench:window")
    evs = [e for dev in trace["devices"].values() for e in xplane.clip(dev, lo, hi)]
    calls = [(kernelmodel.parse(n), e - s) for n, s, e in evs]
    return [(c, t) for c, t in calls if c], hi - lo
