"""test_control.py's whole-run check for the 2-AZ production LRC cell: the
sound run is correct, and each control (a wrong parity byte a stripe; every
stripe acknowledged with shards 33..37 dropped, one global short of
EC16P20L2's quorum of 34) is not. Under `short_quorum` the scheduler finds the
dropped shards and repairs them; where that repair's programs compile inside
the measured window the harness prints no result at all (exit non-zero), which
is the other way such a run may end: never `correct: true`."""
import json
import os
import subprocess
import sys

import pytest

from test_control import ROOT

REFUSED = "compiled inside the measured window: no result"


def run(*extra):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "az2.put16m", "--seed", "2147483999",
         "--seconds", "2", "--trace", "0", "--rehearse-cpu", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=600)
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return p, lines[-1], {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("control,broken_check", [
    (None, None),
    ("parity_flip", "shards_differing_from_reference"),
    ("short_quorum", "min_shards_over_put_quorum"),
])
def test_az2_control_comes_out_not_correct(control, broken_check):
    p, last, checks = run(*(["--control", control] if control else []))
    if control == "short_quorum" and p.returncode != 0:
        assert REFUSED in p.stderr and "correct" not in last
        return
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu" and last["platform"] == "cpu"
    assert "put_MBps" in last["metrics"] and "setup_s" in last["metrics"]
    if control is None:
        assert last["correct"] is True and all(c["ok"] for c in checks.values())
        assert checks["min_shards_over_put_quorum"]["value"] == 4  # 38 stored, quorum 34
    else:
        assert last["correct"] is False
        assert checks[broken_check]["ok"] is False
