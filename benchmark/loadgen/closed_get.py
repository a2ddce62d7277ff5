"""Closed loop of GETs over a data set PUT in set-up: each stream reads a
seeded-uniform object when its last read has returned, and compares every body
with the bytes that were put (a memcmp against the pool, no hash, no copy).
It also says what it holds its own interpreter lock for: `compare_ms`, a
stream's time inside payloads.matches a GET, and `turnaround_ms`, from a body's
last byte to the next request's first (the compare, the op record, the draw),
each as mean and max over every GET of the run.
Parameters: streams, object_bytes, objects, load_streams, stagger_s."""

from __future__ import annotations

import numpy as np

import payloads
import wire
from genlib import LOAD_A, bulk_put, mean_max_ms, now, run_threads, sleep_until


class Generator:
    def __init__(self, spec: dict):
        self.spec, self.p = spec, spec["params"]

    def prepare(self) -> None:
        self.pool = payloads.bases(self.spec["seed"], self.p["object_bytes"])

    def load(self) -> dict:
        p = self.p
        self.loaded = bulk_put(self.spec["addr"], self.pool, self.spec["seed"],
                               [p["object_bytes"]] * p["objects"], p["load_streams"])
        return self.loaded

    def run(self, start: float, t0: float, t1: float) -> dict:
        p, seed = self.p, self.spec["seed"]
        tokens, size = self.loaded["locations"], p["object_bytes"]
        ops: list[list[dict]] = [[] for _ in range(p["streams"])]
        compare_s: list[float] = []  # every stream appends: one list each, for the whole run
        turnaround_s: list[float] = []

        def stream(s: int) -> None:
            c = wire.Client(self.spec["addr"])
            rng = np.random.default_rng([seed, 0x6E7, s])
            sleep_until(start + s * p["stagger_s"])
            q = 0
            while now() < t1:
                i = int(rng.integers(len(tokens)))
                rec = {"stream": s, "kind": "get", "bytes": size, "a": LOAD_A, "b": i,
                       "seq": q, "ok": False, "t_due": now()}
                rec["t_start"] = rec["t_due"]
                if ops[s]:
                    turnaround_s.append(rec["t_due"] - ops[s][-1]["t_end"])
                try:
                    body = c.get(tokens[i])
                    rec["t_end"] = now()
                    rec["ok"] = payloads.matches(body, self.pool, seed, LOAD_A, i, size)
                    compare_s.append(now() - rec["t_end"])
                    if not rec["ok"]:
                        rec["err"] = "body differs from the bytes put"
                    del body
                except wire.WireError as e:
                    rec["t_end"] = now()
                    rec["err"] = str(e)
                ops[s].append(rec)
                q += 1
            c.close()

        run_threads(p["streams"], stream, "get")
        return {"ops": [o for s in ops for o in s], "pool_bytes": p["object_bytes"],
                "compare_ms": mean_max_ms(compare_s), "turnaround_ms": mean_max_ms(turnaround_s)}
