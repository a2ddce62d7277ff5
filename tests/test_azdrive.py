"""tools/azdrive.py end to end on the CPU: the 2-AZ deployment booted as the
daemon boots it, PUT / GET / node-down GET / AZ-down GET through the gateway."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_azdrive_two_az_deployment_reads_back_with_an_az_down(tmp_path):
    p = subprocess.run(
        [sys.executable, "-m", "chubaofs_tpu.tools.azdrive", "--root", str(tmp_path / "blob"),
         "--jax-platform", "cpu", "--sizes", "70000,1100000", "--seed", "2147483999"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"] is True and out["boot"]["platform"] == "cpu"
    assert [(o["mode"], o["shards_a_blob"]) for o in out["objects"]] == \
        [("EC6P10L2", 18), ("EC16P20L2", 38)]
    steps = out["steps"]
    assert [s["step"] for s in steps] == ["healthy", "node_down", "node_down", "az_down"]
    assert all(s["differing"] == 0 for s in steps)
    assert steps[0]["decoded_bytes"] == 0 and all(s["decoded_bytes"] > 0 for s in steps[1:])
    # one whole AZ, the one the benchmark's AZ-down cell loses: both read it
    # from the cell's configuration file
    with open(os.path.join(ROOT, "benchmark", "configs", "az2-ec16p20l2-azdown.json")) as f:
        assert steps[-1]["nodes_down"] == json.load(f)["failure"]["nodes"] == [1, 3, 5, 7, 9, 11]
