"""The generators against a fake gateway: the open loop times from the due
time, every seed offers the same work in another order, payloads rebuild."""
import http.server
import json
import threading
import time

import pytest

import closed_get
import genlib
import open_mixed
import payloads
import window


class FakeGateway(http.server.BaseHTTPRequestHandler):
    """PUT /put stores the body; POST /get returns it; each op takes `delay`,
    and one op at a time is served (a server that saturates at 1/delay)."""
    store: dict = {}
    delay = 0.02
    gate = threading.Lock()
    protocol_version = "HTTP/1.1"

    def _reply(self, body: bytes):
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_PUT(self):
        data = self.rfile.read(int(self.headers["Content-Length"]))
        with self.gate:
            time.sleep(self.delay)
            token = json.dumps({"id": len(self.store)})
            self.store[token] = data
        self._reply(token.encode())

    def do_POST(self):
        req = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        with self.gate:
            time.sleep(self.delay)
        self._reply(self.store[req["location"]])

    def log_message(self, *a):
        pass


@pytest.fixture()
def gateway():
    FakeGateway.store = {}
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), FakeGateway)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)


PARAMS = {"rate_per_s": 100.0, "put_share": 0.5, "keys": 16, "zipf_s": 0.99,
          "sizes": [4096, 8192], "size_weights": [2, 1], "workers": 8, "load_streams": 2}


def spec(addr, seed, **over):
    return {"addr": addr, "seed": seed, "params": dict(PARAMS, **over), "warm_s": 0.2, "seconds": 1.0}


def test_open_loop_times_from_the_due_time(gateway):
    # offered 100 op/s against a server that does 50: the queue grows, so
    # latency from the due time grows far past the 20 ms an op takes
    gen = open_mixed.Generator(spec(gateway, 7))
    gen.prepare()
    gen.load()
    start = genlib.now() + 0.05
    res = gen.run(start, start + 0.2, start + 1.2)
    ops = res["ops"]
    assert ops and all(o["ok"] for o in ops)
    assert all(o["t_start"] >= o["t_due"] for o in ops)
    from_due = window.due_latencies_ms(ops, start + 0.2, start + 1.2)
    own = [(o["t_end"] - o["t_start"]) * 1e3 for o in ops]
    assert window.percentile(from_due, 95) > 250
    assert window.percentile(from_due, 95) > 3 * window.percentile(own, 50)
    assert res["lateness_ms"]["max"] >= 0


def test_every_seed_offers_the_same_work_in_another_order():
    a, b = open_mixed.Generator(spec("x:1", 1)), open_mixed.Generator(spec("x:1", 2 ** 31 + 5))
    a.prepare()
    b.prepare()
    for key in ("kind", "size"):
        assert sorted(o[key] for o in a.schedule) == sorted(o[key] for o in b.schedule)
    assert [o["size"] for o in a.schedule] != [o["size"] for o in b.schedule]
    def gaps(g):
        due = [0.0] + [o["due"] for o in g.schedule]
        return sorted(y - x for x, y in zip(due, due[1:]))

    assert gaps(a) == pytest.approx(gaps(b), abs=1e-6)
    assert all(y["due"] >= x["due"] for x, y in zip(a.schedule, a.schedule[1:]))
    puts = sum(o["kind"] == "put" for o in a.schedule)
    assert puts == len(a.schedule) // 2


def test_multiset_is_exact_and_zipf_skews():
    assert genlib.multiset(["a", "b"], [3, 1], 8) == ["a"] * 6 + ["b"] * 2
    ranks = genlib.multiset(list(range(8)), genlib.zipf_weights(8, 0.99), 1000)
    assert len(ranks) == 1000 and ranks.count(0) > 2.5 * ranks.count(3)


def test_payload_rebuilds_and_matches():
    pool = payloads.bases(99, 1 << 16)
    body = payloads.payload(pool, 99, 3, 41, 5000)
    assert len(body) == 5000 and body == payloads.payload(pool, 99, 3, 41, 5000)
    assert payloads.matches(body, pool, 99, 3, 41, 5000)
    assert not payloads.matches(body, pool, 99, 3, 42, 5000)
    assert not payloads.matches(body[:-1] + bytes([body[-1] ^ 1]), pool, 99, 3, 41, 5000)
    whole = payloads.payload(pool, 99, 0, 1, 1 << 16)
    assert whole[16:] == pool[payloads.base_index(0, 1)][16:]


# -- payloads.matches: every byte of every body, whatever holds the bytes -------------

SIZES = {5000: 1 << 16, 1 << 24: 1 << 24}  # object size -> the pool's base size (a 16 MiB object IS a base)


@pytest.fixture(scope="module")
def pools():
    return {size: payloads.bases(41, base) for size, base in SIZES.items()}


def flip(at):
    def damage(body):
        out = bytearray(body)
        out[at] ^= 1
        return out
    return damage


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("name,damage,same", [
    ("bytes", bytes, True), ("bytearray", bytearray, True), ("memoryview", memoryview, True),
    ("first byte after the stamp", flip(payloads.STAMP_LEN), False), ("last byte", flip(-1), False),
    ("a stamp byte", flip(3), False), ("one byte short", lambda b: b[:-1], False),
    ("one byte long", lambda b: b + b"\0", False),
])
def test_matches_compares_every_byte_of_any_buffer(pools, size, name, damage, same):
    pool = pools[size]
    body = payloads.payload(pool, 41, genlib.LOAD_A, 7, size)
    assert len(body) == size
    assert payloads.matches(damage(body), pool, 41, genlib.LOAD_A, 7, size) is same, name
    assert not payloads.matches(damage(body), pool, 41, genlib.LOAD_A, 8, size)  # another object's bytes


def test_closed_get_says_what_it_holds_its_lock_for(gateway):
    # 2 streams over 3 loaded objects for half a second: every body compared, and the
    # generator's own compare and turnaround come back beside the ops
    gen = closed_get.Generator({"addr": gateway, "seed": 5, "params": {
        "streams": 2, "object_bytes": 1 << 16, "objects": 3, "load_streams": 2, "stagger_s": 0.01}})
    gen.prepare()
    assert gen.load()["failed"] == []
    start = genlib.now() + 0.05
    res = gen.run(start, start + 0.1, start + 0.5)
    gets = res["ops"]
    assert len(gets) >= 4 and all(o["ok"] for o in gets)
    cmp_ms, turn_ms = res["compare_ms"], res["turnaround_ms"]
    assert cmp_ms["count"] == len(gets) and turn_ms["count"] == len(gets) - 2  # none before a stream's first
    assert 0 < cmp_ms["mean"] <= cmp_ms["max"] < 50 and 0 < turn_ms["mean"] <= turn_ms["max"]
    # a body that differs is said so, op by op
    FakeGateway.store = {k: bytes(len(v)) for k, v in FakeGateway.store.items()}
    bad = gen.run(genlib.now(), 0, genlib.now() + 0.2)["ops"]
    assert bad and all(o["err"] == "body differs from the bytes put" and not o["ok"] for o in bad)
