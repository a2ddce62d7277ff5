"""Closed loop of PUTs: each stream sends its next object when the last is
acknowledged. Parameters: streams, object_bytes, stagger_s."""

from __future__ import annotations

import payloads
import wire
from genlib import now, run_threads, sleep_until


class Generator:
    def __init__(self, spec: dict):
        self.spec, self.p = spec, spec["params"]

    def prepare(self) -> None:
        p, seed = self.p, self.spec["seed"]
        self.pool = payloads.bases(seed, p["object_bytes"])
        # each stream owns its send buffers and stamps them in place: no
        # allocation, copy or hash of a payload inside the window
        self.bufs = [[bytearray(self.pool[payloads.base_index(s, q)]) for q in range(payloads.N_BASES)]
                     for s in range(p["streams"])]

    def run(self, start: float, t0: float, t1: float) -> dict:
        p, seed = self.p, self.spec["seed"]
        ops: list[list[dict]] = [[] for _ in range(p["streams"])]

        def stream(s: int) -> None:
            c = wire.Client(self.spec["addr"])
            sleep_until(start + s * p["stagger_s"])
            q = 0
            while now() < t1:
                buf = self.bufs[s][q % payloads.N_BASES]
                buf[: payloads.STAMP_LEN] = payloads.stamp(seed, s, q)
                rec = {"stream": s, "kind": "put", "bytes": len(buf), "a": s, "b": q,
                       "ok": False, "t_due": now()}
                rec["t_start"] = rec["t_due"]
                try:
                    rec["loc"] = c.put(buf)
                    rec["ok"] = True
                except wire.WireError as e:
                    rec["err"] = str(e)
                rec["t_end"] = now()
                ops[s].append(rec)
                q += 1
            c.close()

        run_threads(p["streams"], stream, "put")
        return {"ops": [o for s in ops for o in s], "pool_bytes": p["object_bytes"]}
