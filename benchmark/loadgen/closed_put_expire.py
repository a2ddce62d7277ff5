"""closed_put's loop under a retention policy: set-up loads an OLD generation
(closed_get's load), and after every acknowledged PUT the stream sends one
`POST /delete` of the oldest object of that generation not yet deleted (one
cursor shared by the streams, op kind "delete", ok iff 200). Bytes in = bytes
expired, self-paced: no rate parameter. From `start`, not from `t0`: the warm
seconds of the cell's own traffic expire too. The window's own objects are
never deleted, so verify.py holds for them what it holds for closed_put.

One more thread probes, `probes_per_s` times a second from `start` to `t1`,
alternately: kind "probe", a seeded pick among the objects whose DELETE was
acknowledged more than `apply_within_s` ago (ok iff the gateway answers 404:
a body, or any other status, is a failed op whose `err` says which), and kind
"probe_live", a seeded pick among the loaded objects at least `live_margin`
places past the cursor, none of them due while the GET runs (ok iff 200 and
the body is the bytes that were put): the live neighbours of punched records.
They hold the delete guarantees, they are not load: `bytes` is 0 on deletes
and probes alike, so no throughput reducer counts them, and none is named
"get". A "probe" turn with no DELETE old enough yet probes a live object
instead; a turn with nothing left to pick probes nothing.

The loaded generation has to outlast the run: a stream that finds the cursor
at its end records a failed delete ("the loaded generation is exhausted").

A program without the reclaim plane ends the generator in prepare(), inside
set-up and before any data is loaded: GET /metrics must render every series
of `require_series` (the deleter's backlog gauge, which a program that applies
deletes once a tick of its inspector does not have).

Parameters: closed_put's (streams, object_bytes, stagger_s), closed_get's
load (objects, load_streams), apply_within_s, probes_per_s, live_margin,
require_series."""

from __future__ import annotations

import json
import threading

import numpy as np

import closed_put
import payloads
import wire
from genlib import LOAD_A, bulk_put, now, run_threads, sleep_until


def get_status(client: wire.Client, token: str) -> tuple[int, bytes]:
    """POST /get as wire.Client.get sends it; the status with the body."""
    req = json.dumps({"location": token, "offset": 0, "size": -1}).encode()
    return client._request("POST", "/get", req)


class Generator(closed_put.Generator):
    def prepare(self) -> None:
        wanted = [s.encode() for s in self.p.get("require_series", [])]
        if wanted:
            c = wire.Client(self.spec["addr"])
            try:
                status, text = c._request("GET", "/metrics", None)
            finally:
                c.close()
            missing = [s.decode() for s in wanted if s not in text]
            if status != 200 or missing:
                raise SystemExit(f"the program renders no {missing} (GET /metrics -> {status}): it has no "
                                 "reclaim plane that keeps pace with ingest, this cell cannot run on it")
        super().prepare()

    def load(self) -> dict:
        p = self.p
        self.loaded = bulk_put(self.spec["addr"], self.pool, self.spec["seed"],
                               [p["object_bytes"]] * p["objects"], p["load_streams"])
        return self.loaded

    def run(self, start: float, t0: float, t1: float) -> dict:
        p, seed = self.p, self.spec["seed"]
        tokens, size = self.loaded["locations"], p["object_bytes"]
        ops: list[list[dict]] = [[] for _ in range(p["streams"] + 1)]
        lock = threading.Lock()
        cursor = [0]  # the next object of the old generation to expire
        acked: list[tuple[float, int]] = []  # (when its DELETE was acknowledged, which), in time order

        def expire(c: wire.Client, s: int) -> dict:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            rec = {"stream": s, "kind": "delete", "bytes": 0, "a": LOAD_A, "b": i,
                   "ok": False, "t_due": now()}
            rec["t_start"] = rec["t_due"]
            if i >= len(tokens):
                rec["err"] = "the loaded generation is exhausted: nothing left to expire"
            else:
                try:
                    status, body = c._request("POST", "/delete", tokens[i].encode())
                    rec["ok"] = status == 200
                    if not rec["ok"]:
                        rec["err"] = f"delete -> {status} {body[:200]!r}"
                except wire.WireError as e:
                    rec["err"] = str(e)
            rec["t_end"] = now()
            if rec["ok"]:
                with lock:
                    acked.append((rec["t_end"], i))
            return rec

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
                if rec["ok"]:
                    ops[s].append(expire(c, s))
                q += 1
            c.close()

        def probe(c: wire.Client, kind: str, i: int, due: float) -> dict:
            rec = {"stream": p["streams"], "kind": kind, "bytes": 0, "a": LOAD_A, "b": i,
                   "ok": False, "t_due": due, "t_start": now()}
            try:
                status, body = get_status(c, tokens[i])
                if kind == "probe":
                    rec["ok"] = status == 404
                    if not rec["ok"]:
                        rec["err"] = (f"a body of {len(body)} bytes for an object deleted {p['apply_within_s']} s ago"
                                      if status == 200 else f"status {status} where not-found was due")
                else:
                    rec["ok"] = status == 200 and payloads.matches(body, self.pool, seed, LOAD_A, i, size)
                    if not rec["ok"]:
                        rec["err"] = (f"status {status} for a live object" if status != 200
                                      else "body differs from the bytes put")
            except wire.WireError as e:
                rec["err"] = str(e)
            rec["t_end"] = now()
            return rec

        def prober() -> None:
            c = wire.Client(self.spec["addr"])
            rng = np.random.default_rng([seed, 0x9B0])
            k = 0
            while (due := start + k / p["probes_per_s"]) < t1:
                sleep_until(due)
                with lock:
                    old = [i for t, i in acked if t <= now() - p["apply_within_s"]]
                    first_live = cursor[0] + p["live_margin"]
                if k % 2 == 0 and old:
                    ops[-1].append(probe(c, "probe", old[int(rng.integers(len(old)))], due))
                elif first_live < len(tokens):
                    ops[-1].append(probe(c, "probe_live", int(rng.integers(first_live, len(tokens))), due))
                k += 1
            c.close()

        probing = threading.Thread(target=prober, name="probe")
        probing.start()
        run_threads(p["streams"], stream, "put")
        probing.join()
        return {"ops": [o for s in ops for o in s], "pool_bytes": p["object_bytes"],
                "expired": min(cursor[0], len(tokens)), "loaded": len(tokens)}
