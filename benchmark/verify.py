"""The comparison that decides `correct`, made after the window has closed on
what the timed path produced: the objects it acknowledged and the shards it
left on the blobnodes, compared with the bytes the seed generates and with
benchmark/reference.py. Every number compared is printed beside its limit.

Guarantees held (stated in the configuration file):
  * every acknowledged object reads back byte-equal;
  * every stripe of an acknowledged object holds at least the mode's put
    quorum of shards;
  * every shard stored equals the reference stripe (data and parity alike);
  * every GET of the window returned the bytes that were put (compared by the
    generator on each body as it arrived);
  * no operation failed."""

from __future__ import annotations

import numpy as np

import payloads
import reference
import wire


def sample_puts(ops: list[dict], t0: float, t1: float, seed: int, n: int, tail_s: float) -> list[dict]:
    """A seeded sample of the window's acknowledged PUTs, plus every PUT
    acknowledged in its last ``tail_s`` seconds (the newest writes are the
    ones a lost flush or a short quorum would hit)."""
    acked = [o for o in ops if o["kind"] == "put" and o["ok"] and t0 <= o["t_end"] <= t1]
    acked.sort(key=lambda o: (o["a"], o["b"]))
    tail = [o for o in acked if o["t_end"] >= t1 - tail_s]
    rest = [o for o in acked if o["t_end"] < t1 - tail_s]
    rng = np.random.default_rng([seed, 0x5A3])
    pick = [rest[i] for i in sorted(rng.choice(len(rest), size=min(n, len(rest)), replace=False))]
    return pick + tail


def check_puts(dep, config: dict, sample: list[dict], pool: list[bytes], seed: int,
               max_stripes: int) -> dict:
    """Read back the sampled objects through the gateway and compare one
    seeded stripe of each (up to max_stripes) with the reference."""
    client = wire.Client(dep.addr)
    rng = np.random.default_rng([seed, 0x57A])
    mismatched_objects = 0
    stripes = bad_shards = 0
    min_margin = None
    for n, o in enumerate(sample):
        want = payloads.payload(pool, seed, o["a"], o["b"], o["bytes"])
        try:
            if client.get(o["loc"]) != want:
                mismatched_objects += 1
        except wire.WireError:
            mismatched_objects += 1
        if n >= max_stripes:
            continue
        blobs = dep.stripes(o["loc"])
        j = int(rng.integers(len(blobs)))
        blob, mode = blobs[j], config["modes"][blobs[j]["mode"]]
        off = sum(b["size"] for b in blobs[:j])
        ref = reference.encode(want[off: off + blob["size"]], mode, config["code"])
        present = [s for s in blob["shards"] if s is not None]
        margin = len(present) - mode["put_quorum"]
        min_margin = margin if min_margin is None else min(min_margin, margin)
        stripes += 1
        for idx, shard in enumerate(blob["shards"]):
            if shard is not None and shard != ref[idx].tobytes():
                bad_shards += 1
    client.close()
    return {"objects_read_back": len(sample), "objects_mismatched": mismatched_objects,
            "stripes_compared": stripes, "shards_differing_from_reference": bad_shards,
            "min_shards_over_put_quorum": min_margin}


def decide(dep, config: dict, traffic: dict, result: dict, t0: float, t1: float, seed: int,
           counters: tuple[dict, dict], say) -> tuple[bool, int, int]:
    """-> (correct, attempted, failed). ``say`` prints one line."""
    ops = result["ops"]
    v = traffic["verify"]
    checks: list[tuple[str, float, str, float]] = []  # name, value, relation, limit
    failed_all = [o for o in ops if not o["ok"]]
    checks.append(("failed_ops", len(failed_all), "<=", 0))
    gets = [o for o in ops if o["kind"] == "get"]
    if gets:
        checks.append(("get_bodies_differing", sum(
            1 for o in gets if o.get("err", "").startswith("body differs")), "<=", 0))
        checks.append(("get_bodies_compared", sum(1 for o in gets if o["ok"]), ">=", 1))
    sample = sample_puts(ops, t0, t1, seed, v["sample_objects"], v["tail_seconds"])
    if any(o["kind"] == "put" for o in ops):
        pool = payloads.bases(seed, result["pool_bytes"])
        got = check_puts(dep, config, sample, pool, seed, v["max_stripes"])
        checks += [("put_objects_read_back", got["objects_read_back"], ">=", 1),
                   ("put_objects_mismatched", got["objects_mismatched"], "<=", 0),
                   ("stripes_compared", got["stripes_compared"], ">=", 1),
                   ("shards_differing_from_reference", got["shards_differing_from_reference"], "<=", 0),
                   ("min_shards_over_put_quorum", -1 if got["min_shards_over_put_quorum"] is None
                    else got["min_shards_over_put_quorum"], ">=", 0)]
    for name, least in v.get("counter_delta_min", {}).items():
        checks.append((f"delta:{name}", counters[1].get(name, 0.0) - counters[0].get(name, 0.0),
                       ">=", least))
    correct = True
    for name, value, rel, limit in checks:
        ok = value <= limit if rel == "<=" else value >= limit
        correct = correct and ok
        say(check=name, value=value, limit=f"{rel} {limit}", ok=ok)
    for o in failed_all[:5]:
        say(failed_op={k: o.get(k) for k in ("kind", "stream", "a", "b", "err")})
    window_ops = [o for o in ops if t0 <= o["t_due"] <= t1]
    return correct, len(window_ops), sum(1 for o in window_ops if not o["ok"])
