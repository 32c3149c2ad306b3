"""chip_smoke.py's phases at tiny size on the host CPU backend.

These check the phases' control flow and comparison code — that a phase
runs end to end through the public entry points and that its bit-exact
checks catch a difference. They are not chip evidence: on the CPU the
dense phases run the jnp scan (or, under block_override, the interpret-mode
kernel), and the device-only checks are switched off.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core import program as program_mod  # noqa: E402
from repro.kernels import block_override  # noqa: E402


def test_dense_phase_tiny():
    out = chip_smoke.phase_dense(groups=256, chunks=3, chunk_t=16, blocks=2,
                                 width=64, require_kernel=False)
    assert out["lanes"] == 512 and out["lanes_checked"] == 128
    assert out["dp_replay_bit_exact"] and out["live_reads"] >= 1


def test_families_phase_tiny_through_interpret_kernel():
    """Under block_override the facade runs the interpret-mode DMA kernel
    at the blocks the tuner picks for tpu-v5e, so the phase's CPU-scan
    comparison checks the kernel body for every family."""
    with block_override(autotune_hw="tpu-v5e"):
        out = chip_smoke.phase_families(groups=384, chunk_t=32, blocks=2,
                                        width=64, require_kernel=False)
    assert set(out["families"]) == set(program_mod.registered_families())
    assert all(v["bit_exact"] for v in out["families"].values())


def test_sparse_phase_tiny():
    out = chip_smoke.phase_sparse(capacity=2048, n_routes=1500, flushes=2,
                                  events=1024, read_routes=4)
    assert out["lanes"] == 2048 * 3
    assert out["cpu_replay_bit_exact"] and out["xla_scatter"]
    assert not out["tpu_custom_call"]


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_assert_bit_exact_catches_one_flipped_bit(dtype):
    a = np.arange(64, dtype=dtype)
    chip_smoke.assert_bit_exact("same", a, a.copy())
    b = a.copy()
    b.view(np.uint32)[17] ^= 1
    with pytest.raises(AssertionError, match="1 of 64 elements differ"):
        chip_smoke.assert_bit_exact("flipped", b, a)
    with pytest.raises(AssertionError):
        chip_smoke.assert_bit_exact("dtype", a.astype(np.float64), a)


def test_assert_bit_exact_tells_nan_payloads_apart():
    a = np.full(4, np.nan, np.float32)
    chip_smoke.assert_bit_exact("nan", a, a.copy())
    b = a.copy()
    b.view(np.uint32)[0] |= 1
    with pytest.raises(AssertionError):
        chip_smoke.assert_bit_exact("nan payload", b, a)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == "" and "needs a TPU" in captured.err


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    res = _run(["chip_smoke.py"], cwd=tmp_path, env_extra={"PYTHONPATH": ""})
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_topologies_phase_on_four_host_devices():
    """The --chips 4 phase on four forced host devices: both topologies
    resolve to shard_map and match one device bit for bit."""
    code = ("import chip_smoke as cs; "
            "cs.run_phase('topologies', cs.phase_topologies, "
            "cs.CompileClock(), groups=512, chunks=5, chunk_t=16)")
    res = _run(["-c", code], cwd=ROOT, env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert res.returncode == 0, res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["lanes4"] == {"devices": 4, "bit_exact": True}
    assert line["data2_lanes2"] == {"mode": "shard_map", "bit_exact": True}
