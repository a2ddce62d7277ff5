"""test_control.py's whole-run check for the AZ-down cell of the 2-AZ production
LRC deployment: the sound rehearsal is correct (every body compared, at least
500 MB of them rebuilt by decode: the CPU needs some 40 s of window for that),
and `parity_flip`, a wrong byte in every decoded blob, is not, by the bodies the
generator compared."""
import json
import os
import subprocess
import sys

import pytest

from test_control import ROOT


def run(seconds, *extra):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "az2.get16m-azdown", "--seed", "2147483999",
         "--seconds", str(seconds), "--trace", "0", "--rehearse-cpu", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900)
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return p, lines[-1], {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("control,seconds", [(None, 40), ("parity_flip", 3)])
def test_azdown_control_comes_out_not_correct(control, seconds):
    p, last, checks = run(seconds, *(["--control", control] if control else []))
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu" and last["platform"] == "cpu"
    assert "get_MBps" in last["metrics"] and "setup_s" in last["metrics"]
    if control is None:
        assert last["failed"] == 0
        assert last["correct"] is True and all(c["ok"] for c in checks.values())
        assert checks['delta:cfs_access_read_bytes{kind="decoded"}']["value"] >= 500_000_000
    else:
        assert last["correct"] is False and last["control"] == control
        assert checks["get_bodies_differing"]["ok"] is False
