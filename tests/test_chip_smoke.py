"""chip_smoke.py's CPU dry run, and the two start-up rules it leans on:
the device is explicit (no accelerator and no explicit CPU -> refuse) and
the compile cache can be placed from outside (JAX_COMPILATION_CACHE_DIR
wins and is never overridden; unset -> <checkout>/.jax_cache)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def test_chip_smoke_cpu_dry_run():
    """The whole smoke at --scale tiny: the parent stays off jax, the
    server child runs on JAX_PLATFORMS=cpu, every oracle comparison
    holds, and the output says CPU in so many words."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)  # conftest's: tests only
    p = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--allow-cpu",
         "--scale", "tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "CPU DRY RUN" in p.stdout
    lines = p.stdout.strip().splitlines()
    # the verdict line carries exactly these keys (the chip check's
    # contract); everything else is in the summary line before it
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    summary = json.loads(lines[-2])
    assert summary["cpu_dry_run"] is True and summary["failures"] == []
    assert summary["device"] == last["device"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_chip_smoke_refuses_cpu_without_allow_cpu():
    """In a sandbox with no accelerator the smoke fails and prints no
    result object: a CPU server is not a chip result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--scale", "tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_device_rule_refuses_silent_cpu():
    from tempo_tpu.util.costmodel import NoAcceleratorError, check_device

    check_device("tpu", "tpu,cpu")
    check_device("tpu", None)
    check_device("cpu", "cpu")
    for asked in (None, "", "tpu,cpu"):
        with pytest.raises(NoAcceleratorError, match="no accelerator"):
            check_device("cpu", asked)


def test_app_refuses_to_start_without_device(monkeypatch, tmp_path):
    """jax fell back to the host and nobody asked for the CPU: the
    server stops with a message naming the missing device instead of
    serving from the CPU without a word."""
    import jax

    from tempo_tpu.services.app import App, AppConfig
    from tempo_tpu.util import costmodel

    monkeypatch.setattr(costmodel, "_device", None)
    asked = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(costmodel.NoAcceleratorError,
                           match="JAX_PLATFORMS=cpu"):
            App(AppConfig(target="all", storage_path=str(tmp_path)))
    finally:
        jax.config.update("jax_platforms", asked)
    # asked for explicitly, the same start resolves and names the CPU
    assert costmodel.resolve_device()["platform"] == "cpu"


def test_compile_cache_placed_by_jax_variable(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it, the program reports
    it and never points the cache anywhere else."""
    import jax

    from tempo_tpu.util import costmodel

    calls = []
    real_update = jax.config.update
    monkeypatch.setenv(costmodel.JAX_CACHE_ENV, "/x")
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (calls.append(name), real_update(name, val)))
    try:
        assert costmodel.enable_compile_cache("/elsewhere") == "/x"
        assert costmodel.compile_cache_stats()["dir"] == "/x"
        assert "jax_compilation_cache_dir" not in calls
        assert "jax_persistent_cache_min_compile_time_secs" in calls
    finally:
        monkeypatch.undo()
        costmodel.enable_compile_cache()


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch):
    import jax

    from tempo_tpu.util import costmodel

    monkeypatch.delenv(costmodel.JAX_CACHE_ENV, raising=False)
    want = str(REPO / ".jax_cache")
    assert costmodel.DEFAULT_COMPILE_CACHE_DIR == want
    assert costmodel.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert costmodel.compile_cache_stats()["dir"] == want
