"""What the generator kinds share: the clock, bulk loading, seeded multisets."""

from __future__ import annotations

import math
import threading
import time

import numpy as np

import payloads
import wire

now = time.monotonic
LOAD_A = 1_000_000  # the `a` of objects loaded in set-up


def sleep_until(t: float) -> None:
    while True:
        d = t - now()
        if d <= 0:
            return
        time.sleep(d)


def mean_max_ms(seconds: list[float]) -> dict:
    """{"mean", "max", "count"} of a generator's own intervals, in ms."""
    if not seconds:
        return {}
    return {"mean": sum(seconds) / len(seconds) * 1e3, "max": max(seconds) * 1e3, "count": len(seconds)}


def run_threads(n: int, target, name: str) -> None:
    """Run target(0..n-1) on n threads and wait for all of them."""
    threads = [threading.Thread(target=target, args=(i,), name=f"{name}{i}") for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def bulk_put(addr: str, pool: list[bytes], seed: int, sizes: list[int], streams: int) -> dict:
    """PUT object (LOAD_A, i) of sizes[i] for every i, over ``streams``
    connections. Set-up work: not timed as traffic."""
    tokens: list[str | None] = [None] * len(sizes)
    failed: list[str] = []
    nxt = iter(range(len(sizes)))
    lock = threading.Lock()

    def worker(_: int) -> None:
        c = wire.Client(addr)
        while True:
            with lock:
                i = next(nxt, None)
            if i is None:
                break
            try:
                tokens[i] = c.put(payloads.payload(pool, seed, LOAD_A, i, sizes[i]))
            except wire.WireError as e:
                failed.append(f"{i}: {e}")
        c.close()

    t0 = now()
    run_threads(streams, worker, "load")
    return {"locations": tokens, "sizes": sizes, "bytes": sum(sizes),
            "seconds": now() - t0, "failed": failed}


def multiset(values: list, weights: list[float], n: int) -> list:
    """n items holding each value in exact proportion to its weight (largest
    remainder), in value order: every seed gets the same multiset, and the seed
    only permutes it."""
    total = float(sum(weights))
    exact = [n * w / total for w in weights]
    counts = [math.floor(x) for x in exact]
    for i in sorted(range(len(exact)), key=lambda i: exact[i] - counts[i], reverse=True)[: n - sum(counts)]:
        counts[i] += 1
    out = []
    for v, c in zip(values, counts):
        out.extend([v] * c)
    return out


def permuted(items: list, rng: np.random.Generator) -> list:
    return [items[i] for i in rng.permutation(len(items))]


def zipf_weights(n: int, s: float) -> list[float]:
    return [1.0 / (r ** s) for r in range(1, n + 1)]


def exponential_gaps(n: int, rate: float) -> list[float]:
    """The n quantile midpoints of the exponential distribution of mean
    1/rate: a Poisson process's gaps as a fixed multiset."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
