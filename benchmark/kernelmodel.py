"""Bytes and operations of one fused GF(2^8) kernel call, from its traced
shapes alone. The kernel (ops/pallas_gf.py) reads a (b, n, k) uint8 batch and a
(8r, 8n) int8 bit matrix from HBM and writes (b, r, k) uint8; per byte column it
does an (8r x 8n) by (8n) int8 multiply-accumulate on the MXU.

In a trace the call is the HLO op
  %_fused_core.N = u8[b,r,k]{..} custom-call(s8[8r,8n]{..} %mat.., u8[b,n,k]{..} ..)
(with group stacking b, r and n are the stacked ones: b/g, g*r, g*n)."""

from __future__ import annotations

import re

KERNEL = re.compile(r"^%_fused_core\S* = u8\[(\d+),(\d+),(\d+)\]\S* custom-call\(s8\[(\d+),(\d+)\]")


def parse(op_name: str) -> dict | None:
    m = KERNEL.match(op_name)
    if not m:
        return None
    b, r, k, r8, n8 = map(int, m.groups())
    n = n8 // 8
    return {"b": b, "r": r, "n": n, "k": k,
            "bytes": b * (n + r) * k + r8 * n8,   # batch in + result out + matrix
            "ops": 2 * r8 * n8 * k * b}           # int8 multiply-accumulates, as executed


def least_seconds(call: dict, peaks: dict) -> float:
    """The least time the chip could take for the call's HBM traffic. The
    bytes are what the algorithm needs (every payload byte in once, every
    result byte out once). The executed operations are NOT what it needs: a
    group-stacked matrix is block-diagonal, g-1 of its g blocks are zeros, so
    the operation count above overstates the need by g and is reported only
    as a second figure. On needed work both deployments are HBM-bound."""
    return call["bytes"] / peaks["hbm_bytes_per_s"]
