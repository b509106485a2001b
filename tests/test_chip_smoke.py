"""chip_smoke.py's contract off the chip, and the compile-cache helper.

Without a TPU the script must exit non-zero and print no result; with
``--rehearse`` it runs every phase on the CPU and every check passes, but its
last line says ``"ok": false``.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import runtime

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _run(args, cwd, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return subprocess.run([sys.executable, str(cwd / "chip_smoke.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _json_lines(stdout):
    return [line for line in stdout.splitlines() if line.startswith("{")]


def test_smoke_without_tpu_fails_and_prints_no_result(tmp_path):
    out = _run([], ROOT, tmp_path)
    assert out.returncode != 0
    assert _json_lines(out.stdout) == []
    assert "no TPU" in out.stderr


def test_smoke_alone_fails_and_prints_no_result(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone / "chip_smoke.py")
    out = _run([], alone, tmp_path)
    assert out.returncode != 0
    assert _json_lines(out.stdout) == []


@pytest.mark.parametrize("chips,docs", [(1, 4096), (4, 16384)],
                         ids=["one_device", "sharded4"])
def test_smoke_rehearsal_passes_every_check_but_not_ok(tmp_path, chips,
                                                       docs):
    out = _run(["--rehearse", "--chips", str(chips), "--docs", str(docs)],
               ROOT, tmp_path)
    assert out.returncode == 1, out.stderr[-2000:]
    checks = [line for line in out.stdout.splitlines()
              if line.startswith("check ")]
    assert checks and all(line.split(": ")[1].startswith("PASS")
                          for line in checks), checks
    for name in ("pallas ids == reference ids",
                 "returned scores == exact scan scores"):
        assert f"check {name}: PASS" in out.stdout
    if chips > 1:
        assert "check state spread over distinct devices: PASS" in out.stdout
    else:
        assert "check front door answers == query_many answers: PASS" \
            in out.stdout
    last = json.loads(out.stdout.splitlines()[-1])
    assert last == {"ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                            "count": chips}}


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    got = runtime.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               cache_config):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
