"""chip_smoke.py off the chip, and the compile-cache placement.

The smoke itself is the chip check; what tier-1 can hold it to on the
CPU is that its rehearsal passes end to end (every answer equal to the
plain reference, device routes exercised on the CPU backend, clean
shutdown) while SAYING it is a rehearsal, that the default invocation
refuses a machine without a TPU instead of falling back, and that the
persistent compilation cache goes where it is told.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout):
    return subprocess.run(
        [sys.executable, SMOKE, *args], cwd=REPO, capture_output=True,
        text=True, timeout=timeout,
    )


def test_rehearsal_passes_and_says_so():
    proc = _run("--rehearse-cpu", timeout=700)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # the last line is the result: these keys and no others
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert isinstance(result["device"]["count"], int)
    # the line before it carries the facts of the run
    out = json.loads(lines[-2])["facts"]
    assert out["platform"] == "cpu" and out["rehearsal"] is True
    assert out["answers_matched"] > 100
    assert out["answers_matched"] == (
        out["requests_sent"] - out["retried_429_503_504"]
    )
    assert out["plans"]["device"] + out["plans"]["resident"] > 0
    assert out["device_batches"] + out["aot_hits"] + out["aot_misses"] > 0
    assert out["shutdown"]["leader_rc"] == 0
    assert out["shutdown"]["workers_stopped"] == 2


def test_default_refuses_a_machine_without_a_tpu():
    if os.path.exists("/dev/accel0") or os.path.exists("/dev/vfio/0"):
        pytest.skip("this machine may hold a TPU")
    proc = _run(timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line
    assert "no TPU" in proc.stderr


def test_compile_cache_env_var_wins():
    from dss_tpu import ops

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/somewhere/else")
        assert ops.place_compile_cache(
            {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}
        ) is None
        assert jax.config.jax_compilation_cache_dir == "/somewhere/else"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_default_is_fixed_in_the_checkout(tmp_path):
    code = (
        "import os, jax, dss_tpu.ops as o; "
        "print(o.place_compile_cache({})); "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env["PYTHONPATH"] = REPO
    want = os.path.join(REPO, ".jax_cache")
    for cwd in (REPO, str(tmp_path)):  # two pids, two cwds, two times
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.split() == [want, want]
