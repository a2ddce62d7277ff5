"""ops/device.py: the placed compile cache, the platform a role may use, and
the daemon's refusal to boot on a platform that is not there."""

import json
import os
import subprocess
import sys

import pytest

from chubaofs_tpu.ops import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(device.jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.setattr(device, "_install_compile_counters", lambda: None)
    return calls


def test_cache_dir_env_wins_and_code_sets_none(monkeypatch, tmp_path):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls  # JAX reads the env itself
    # ...but every served program is still worth writing
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert device.enable_compile_cache() == device.CACHE_DIR
    assert calls["jax_compilation_cache_dir"] == device.CACHE_DIR
    assert device.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


_CHILD = """
import json, jax, jax.numpy as jnp
from chubaofs_tpu.ops import device
path = device.enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print(json.dumps({"path": path, "cfg": jax.config.jax_compilation_cache_dir,
                  **device.compile_stats()}))
"""


def _run_child(env_dir: str | None) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    p = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("placed", [True, False])
def test_second_process_hits_the_cache(tmp_path, placed):
    """Placed from outside or at the fixed default, a second process finds
    what the first compiled (sub-second programs included)."""
    want = str(tmp_path / "cc") if placed else device.CACHE_DIR
    cold = _run_child(want if placed else None)
    assert cold["path"] == cold["cfg"] == want
    assert cold["compiles"] >= 1
    if placed:  # a fresh directory: the first process must have written
        assert cold["cache_writes"] >= 1 and cold["cache_hits"] == 0
    assert any(f.endswith("-cache") for f in os.listdir(want))
    warm = _run_child(want if placed else None)
    assert warm["path"] == want
    assert warm["cache_hits"] >= 1 and warm["cache_writes"] == 0


def test_only_the_blobstore_role_may_leave_the_cpu(monkeypatch):
    from chubaofs_tpu.cmd import ROLES, _jax_platform

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    for role in ROLES:
        got = _jax_platform({"role": role, "jaxPlatform": "tpu"})
        assert got == ("tpu" if role == "blobstore" else "cpu"), role
    # config beats env beats JAX's default
    assert _jax_platform({"role": "blobstore", "jaxPlatform": "cpu"}) == "cpu"
    assert _jax_platform({"role": "blobstore"}) == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert _jax_platform({"role": "blobstore"}) is None


@pytest.mark.skipif(os.path.exists("/dev/accel0") or os.path.exists("/dev/vfio/0"),
                    reason="this host has an accelerator: the daemon would boot")
def test_daemon_configured_for_tpu_refuses_to_boot_without_one(tmp_path):
    cfg = tmp_path / "bs.json"
    cfg.write_text(json.dumps({
        "role": "blobstore", "root": str(tmp_path / "blob"),
        "listen": "127.0.0.1:0", "jaxPlatform": "tpu"}))
    p = subprocess.run(
        [sys.executable, "-m", "chubaofs_tpu.cmd", "-c", str(cfg)],
        env={**os.environ, "PYTHONPATH": REPO}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "Unable to initialize backend 'tpu'" in p.stderr
    assert '"role"' not in p.stdout  # no boot line: it never served


def test_harness_reports_a_daemon_that_dies_at_boot(tmp_path):
    """boot_info must not sit out its timeout on a corpse: the exit code and
    the log tail come back at once (chip_smoke.py reads boot lines this way)."""
    from chubaofs_tpu.testing.harness import ProcCluster

    c = ProcCluster.shell(str(tmp_path))
    try:
        c.spawn("bad", {"role": "no-such-role"})
        with pytest.raises(RuntimeError,
                           match=r"bad exited \d+ before its boot line"):
            c.boot_info("bad", timeout=120)
    finally:
        c.close()
