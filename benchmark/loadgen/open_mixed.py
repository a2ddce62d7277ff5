"""Open loop of small PUTs and GETs at a fixed rate over keys loaded in set-up.

Arrivals are a Poisson process whose gaps, operation kinds, sizes and key ranks
are fixed multisets that the seed only permutes, so every seed offers the same
work in another order. An op is timed from when it was DUE; a GET reads the
newest acknowledged version of its key and compares every body with the bytes
put; a PUT writes a new version of its key (the old one is left for the
deleter, as an overwrite leaves it upstream). Parameters: rate_per_s,
put_share, keys, zipf_s, sizes, size_weights, workers, load_streams."""

from __future__ import annotations

import queue
import threading

import numpy as np

import payloads
import wire
from genlib import (LOAD_A, bulk_put, exponential_gaps, multiset, now, permuted,
                    sleep_until, zipf_weights)

PUT_A = 2_000_000  # the `a` of objects PUT by the schedule
BASE_BYTES = 1 << 20


class Generator:
    def __init__(self, spec: dict):
        self.spec, self.p = spec, spec["params"]

    def prepare(self) -> None:
        p, seed = self.p, self.spec["seed"]
        self.pool = payloads.bases(seed, BASE_BYTES)
        total = self.spec["warm_s"] + self.spec["seconds"]
        n = max(1, round(p["rate_per_s"] * total))
        rng = np.random.default_rng([seed, 0x09E7])
        due = np.cumsum(permuted(exponential_gaps(n, p["rate_per_s"]), rng))
        kinds = permuted(multiset(["put", "get"], [p["put_share"], 1 - p["put_share"]], n), rng)
        sizes = permuted(multiset(p["sizes"], p["size_weights"], n), rng)
        key_of_rank = rng.permutation(p["keys"])
        ranks = permuted(multiset(list(range(p["keys"])), zipf_weights(p["keys"], p["zipf_s"]), n), rng)
        self.schedule = [{"i": i, "due": float(due[i]), "kind": kinds[i], "size": int(sizes[i]),
                          "key": int(key_of_rank[ranks[i]])} for i in range(n)]
        self.load_sizes = multiset(p["sizes"], p["size_weights"], p["keys"])
        # every PUT body is built before the window opens
        self.bodies = {o["i"]: payloads.payload(self.pool, seed, PUT_A, o["i"], o["size"])
                       for o in self.schedule if o["kind"] == "put"}

    def load(self) -> dict:
        loaded = bulk_put(self.spec["addr"], self.pool, self.spec["seed"], self.load_sizes,
                          self.p["load_streams"])
        # key -> (a, b, size, token) of its newest acknowledged version
        self.current = {k: (LOAD_A, k, self.load_sizes[k], tok)
                        for k, tok in enumerate(loaded["locations"])}
        return loaded

    def run(self, start: float, t0: float, t1: float) -> dict:
        seed = self.spec["seed"]
        work: queue.SimpleQueue = queue.SimpleQueue()
        ops: list[dict] = []

        def worker(_: int) -> None:
            c = wire.Client(self.spec["addr"])
            while (o := work.get()) is not None:
                rec = {"stream": 0, "kind": o["kind"], "bytes": o["size"], "key": o["key"],
                       "ok": False, "t_due": start + o["due"], "t_start": now()}
                try:
                    if o["kind"] == "put":
                        rec["a"], rec["b"] = PUT_A, o["i"]
                        rec["loc"] = c.put(self.bodies[o["i"]])
                        rec["t_end"] = now()
                        self.current[o["key"]] = (PUT_A, o["i"], o["size"], rec["loc"])
                        rec["ok"] = True
                    else:
                        a, b, size, token = self.current[o["key"]]
                        rec["a"], rec["b"], rec["bytes"] = a, b, size
                        body = c.get(token)
                        rec["t_end"] = now()
                        rec["ok"] = payloads.matches(body, self.pool, seed, a, b, size)
                        if not rec["ok"]:
                            rec["err"] = "body differs from the bytes put"
                except wire.WireError as e:
                    rec["t_end"] = now()
                    rec["err"] = str(e)
                ops.append(rec)
            c.close()

        threads = [threading.Thread(target=worker, args=(k,), name=f"op{k}") for k in range(self.p["workers"])]
        for t in threads:
            t.start()
        for o in self.schedule:
            if start + o["due"] > t1:
                break
            sleep_until(start + o["due"])
            work.put(o)
        for _ in threads:
            work.put(None)
        for t in threads:
            t.join()
        late = sorted(o["t_start"] - o["t_due"] for o in ops)
        return {"ops": ops, "pool_bytes": BASE_BYTES, "lateness_ms": {
            "p50": late[len(late) // 2] * 1e3, "p95": late[int(len(late) * 0.95)] * 1e3,
            "max": late[-1] * 1e3} if late else {}}
