"""The load generator process. It never imports jax or the program: it speaks
HTTP to the gateway (benchmark/wire.py), so the client's Python does not share
the daemon's interpreter lock, and its cores are disjoint from the daemon's.

Protocol with the harness (one JSON object per line): the harness passes a spec
file; the child prints {"event": "ready"}, then (if the kind loads data)
waits for a line on stdin, loads, writes <out>/load.json and prints
{"event": "loaded", ...}; then it waits for a {"start", "t0", "t1"} line on
stdin (CLOCK_MONOTONIC seconds, shared with the harness), runs, writes
<out>/ops.json and prints {"event": "done"}."""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def load_kind(kind: str):
    path = os.path.join(HERE, kind + ".py")
    spec = importlib.util.spec_from_file_location("loadgen_" + kind, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    if spec.get("cores"):
        os.sched_setaffinity(0, spec["cores"])
    gen = load_kind(spec["kind"]).Generator(spec)
    gen.prepare()
    say(event="ready", cores=sorted(os.sched_getaffinity(0)))
    if hasattr(gen, "load"):
        sys.stdin.readline()  # the harness says when: after its closed-set warm-up, never beside it
        loaded = gen.load()
        with open(os.path.join(spec["out"], "load.json"), "w") as f:
            json.dump(loaded, f)
        say(event="loaded", objects=len(loaded["locations"]), bytes=loaded["bytes"],
            seconds=loaded["seconds"], failed=loaded["failed"])
    gc.collect()
    gc.freeze()  # the pool and schedule never become garbage: keep them out of every later collection
    go = json.loads(sys.stdin.readline())
    result = gen.run(go["start"], go["t0"], go["t1"])
    with open(os.path.join(spec["out"], "ops.json"), "w") as f:
        json.dump(result, f)
    say(event="done", ops=len(result["ops"]))
    return 0


if __name__ == "__main__":
    assert "jax" not in sys.modules
    rc = main(sys.argv)
    assert "jax" not in sys.modules, "the load generator must never import jax"
    sys.exit(rc)
