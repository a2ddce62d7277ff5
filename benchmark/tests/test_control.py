"""A whole run with the timed path broken underneath must report
`correct: false`; the same run unbroken reports true. Drives run.py as the
driver does, except that --rehearse-cpu skips the look for a chip (tiny sizes,
CPU, every line stamped)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(workload, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload, "--seed", "2147483999",
         "--seconds", "2", "--trace", "0", "--rehearse-cpu", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return lines[-1], {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("workload,control,broken_check", [
    ("az1.put16m", None, None),
    ("az1.put16m", "parity_flip", "shards_differing_from_reference"),
    ("az3.put16m", "short_quorum", "min_shards_over_put_quorum"),
    ("az1.get16m-nodedown", "parity_flip", "get_bodies_differing"),
])
def test_control_comes_out_not_correct(workload, control, broken_check):
    last, checks = run(workload, *(["--control", control] if control else []))
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu" and last["platform"] == "cpu"
    if control is None:
        assert last["correct"] is True and all(c["ok"] for c in checks.values())
    else:
        assert last["correct"] is False
        assert checks[broken_check]["ok"] is False
