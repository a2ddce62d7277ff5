"""bench.py helper logic (no device needed): timing statistics, plausibility
floors, and the grouped staging contract the benchmark relies on."""

import numpy as np
import pytest

import bench


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_hbm_peak_known_kind_and_unknown_is_an_error():
    assert bench.hbm_peak(_Dev("TPU v5 lite")) == 819e9
    assert bench.hbm_floor(819e9, _Dev("TPU v5 lite")) == pytest.approx(1.0)
    # a device nobody wrote peaks down for is an error, not "no gate": the
    # CPU backend included, so the bench cannot produce a number off the chip
    for kind in ("mystery accelerator", "cpu", "TPU v4"):
        with pytest.raises(RuntimeError, match="DEVICE_PEAKS"):
            bench.hbm_peak(_Dev(kind))
        with pytest.raises(RuntimeError):
            bench.hbm_floor(1 << 30, _Dev(kind))


def test_bench_refuses_to_run_off_the_chip():
    """`python bench.py` on a CPU backend exits non-zero and prints no result
    line: a device number comes from the chip or not at all."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       capture_output=True, text=True, timeout=120, cwd=repo,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "DEVICE_PEAKS" in p.stderr


def test_throughput_median_rejects_subfloor_passes(monkeypatch):
    """A corrupted (faster-than-physics) pass must not win: throughput() must
    discard sub-floor slopes and report the median of the plausible passes."""
    import itertools

    # fake clock: each timed(n_iters) call consumes one delta; slope of pass
    # p = (delta(n2) - delta(n1)) / 30. Pass 2 is corrupted (near-zero slope).
    # NOTE: throughput() times the n2 leg FIRST, then n1 — pairs below are
    # scripted in call order (delta_n2, delta_n1); slope = (n2 - n1) / 30
    deltas = itertools.chain(
        [0.0],  # warmup timed(2)
        [40e-3, 10e-3] * 3,  # pass 1: slope 1e-3
        [10e-3, 10e-3] * 3,  # pass 2: corrupted — slope 0 (sub-floor)
        [80e-3, 20e-3] * 3,  # pass 3: slope 2e-3
    )
    clock = {"t": 0.0}

    def fake_perf_counter():
        return clock["t"]

    def fake_fn():
        return np.zeros((1, 4))

    # drive timed() by advancing the clock by the scripted delta at the sync
    script = list(deltas)
    idx = {"i": 0}

    def fake_block_until_ready(x):
        if idx["i"] < len(script):
            clock["t"] += script[idx["i"]]
            idx["i"] += 1
        return x

    monkeypatch.setattr(bench.time, "perf_counter", fake_perf_counter)
    monkeypatch.setattr(bench.jax, "block_until_ready", fake_block_until_ready)
    per = bench.throughput(lambda: fake_fn(), (), n1=10, n2=40, runs=3,
                           passes=3, floor=1e-4)
    # plausible slopes {1e-3, 2e-3}; median of the sorted pair = 2e-3
    assert per == pytest.approx(2e-3)


def test_headline_metric_constant_used_everywhere():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(bench))
    # the metric literal may appear ONLY as the constant's assignment; main()
    # must reference HEADLINE_METRIC (comments and docstrings quoting the name
    # are fine — only real string constants count)
    literal_sites = [
        n for n in ast.walk(tree)
        if isinstance(n, ast.Constant) and n.value == bench.HEADLINE_METRIC
    ]
    assert len(literal_sites) == 1, "metric literal duplicated outside constant"
    names = [n.id for n in ast.walk(tree)
             if isinstance(n, ast.Name) and n.id == "HEADLINE_METRIC"]
    assert len(names) >= 2  # definition + main()


def test_stage_grouped_layout_contract(rng):
    """stage_grouped's host view must match rs.group_stack's g for the batch."""
    import jax

    from chubaofs_tpu.ops import rs

    kernel = rs.get_kernel(6, 3)
    host = rng.integers(0, 256, (8, 6, 256), dtype=np.uint8)
    mat_s, data = bench.stage_grouped(jax.devices("cpu")[0], host,
                                      kernel.parity_bits)
    _, g = rs.group_stack(kernel.parity_bits, 8)
    assert data.shape == (8 // g, g * 6, 256)
    assert mat_s.shape == (g * 24, g * 48)
