"""`chip_smoke.py` must FAIL where there is no chip, and the compile
cache must sit where it can be found again.

The smoke's pass is proven on the accelerator (through the chip tool);
what tier-1 pins here is the other half of its contract: no CPU
fallback, and no result line, when JAX finds no TPU or the script is
run outside a checkout."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def test_chip_smoke_fails_fast_without_a_chip(tmp_path):
    if any(os.path.exists(p) for p in ("/dev/accel0", "/dev/vfio/0")):
        pytest.skip("this box has an accelerator")
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, SMOKE, "--out", str(tmp_path)],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert time.monotonic() - t0 < 60
    assert out.stdout == ""                      # no result line
    assert "'tpu' backend" in out.stderr         # names what is missing
    # it gave up BEFORE starting the platform or building anything
    assert sorted(os.listdir(tmp_path)) == ["probe.err"]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "checkout" in out.stderr


def test_last_stdout_line_is_the_drivers_object_and_nothing_more(
        monkeypatch, tmp_path, capsys):
    """The driver refused PR 21's first smoke for extra keys on the last
    line: the run's detail belongs on the line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    probe = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(
        chip_smoke, "run",
        lambda args: {**chip_smoke.result_line(probe), "rounds": 4})
    assert chip_smoke.main(["--out", str(tmp_path)]) == 0
    summary, last = map(json.loads, capsys.readouterr().out.splitlines())
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert summary["summary"]["rounds"] == 4
    assert json.load(open(tmp_path / "summary.json")) == summary["summary"]


@pytest.fixture
def cache_config():
    """Put JAX's cache settings back: the suite must not start caching
    every later test's programs because this one called the helper."""
    keys = ("jax_compilation_cache_dir", "jax_platforms",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_is_placed_from_outside_or_fixed(
        cache_config, monkeypatch, tmp_path):
    from iotml.utils.device import enable_compile_cache

    # set from outside: JAX reads the variable itself, code sets nothing
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # unset, pinned to the CPU: no cache in the checkout (machine-bound
    # programs)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert jax.config.jax_platforms == "cpu"
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    # unset, on an accelerator: one fixed path inside the checkout
    jax.config.update("jax_platforms", "tpu")
    first = enable_compile_cache()
    assert first == enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
