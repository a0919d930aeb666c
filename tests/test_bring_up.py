"""What keeps the program honest about the chip (PR 21): where the compile
cache goes, how stale native libraries are detected, and that the on-chip
smoke refuses to pass anywhere but on an accelerator."""

from __future__ import annotations

import ctypes
import json
import os
import shutil
import subprocess
import sys

import pytest

from fluidframework_tpu.native import _build
from fluidframework_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- compile cache
def test_cache_dir_honours_the_variable_and_is_otherwise_fixed(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # In the checkout, never a temporary, pid- or time-derived path: the
    # directory is part of what the next process must find again.
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_compile_cache")


def test_enable_sets_no_directory_when_the_variable_is_set(monkeypatch):
    import jax

    seen = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (seen.append(name), real(name, value)),
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert seen and "jax_compilation_cache_dir" not in seen
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compile_cache.enable()
    assert "jax_compilation_cache_dir" in seen


def test_only_the_resolver_names_the_cache_option():
    """fleet_main, bench.py, conftest and chip_smoke's children all go
    through utils/compile_cache.py; nothing else may place the cache."""
    files = subprocess.run(
        ["git", "ls-files", "*.py"], cwd=REPO, capture_output=True,
        text=True, check=True,
    ).stdout.split()
    offenders = []
    for rel in files:
        if rel in ("fluidframework_tpu/utils/compile_cache.py",
                   "tests/test_bring_up.py"):
            continue
        with open(os.path.join(REPO, rel)) as f:
            if "jax_compilation_cache_dir" in f.read():
                offenders.append(rel)
    assert offenders == []


# ------------------------------------------------------------ native loaders
@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_native_build_is_keyed_by_source_content_not_mtime(tmp_path):
    src, lib = tmp_path / "probe.cpp", tmp_path / "libprobe.so"
    src.write_text('extern "C" int probe() { return 1; }\n')
    _build.ensure_built(src, lib)
    assert _build.is_current(src, lib)
    assert ctypes.CDLL(str(lib)).probe() == 1

    # New source bytes under a library that LOOKS newer (a copied checkout
    # keeps no meaningful mtimes): the hash, not the clock, must decide.
    src.write_text('extern "C" int probe() { return 2; }\n')
    os.utime(src, (1, 1))
    future = os.stat(lib).st_mtime + 3600
    os.utime(lib, (future, future))
    assert not _build.is_current(src, lib)
    _build.ensure_built(src, lib)
    assert _build.is_current(src, lib)
    # dlopen caches by path: load the rebuilt library through a copy.
    fresh = tmp_path / "libprobe2.so"
    shutil.copy(lib, fresh)
    assert ctypes.CDLL(str(fresh)).probe() == 2

    # A library with no recorded hash is never trusted.
    os.unlink(str(lib) + ".sha256")
    assert not _build.is_current(src, lib)

    src.write_text("this is not C++\n")
    with pytest.raises(_build.NativeBuildError, match="g\\+\\+ failed"):
        _build.ensure_built(src, lib)


# ---------------------------------------------------------------- chip_smoke
def _smoke(*argv, cwd=REPO, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=timeout, cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_chip_smoke_refuses_the_cpu():
    out = _smoke()
    assert out.returncode != 0
    assert "no accelerator (platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout  # no result line at all


def test_chip_smoke_needs_the_repo_beside_it(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _smoke(cwd=str(tmp_path))
    assert out.returncode != 0 and out.stdout == ""


def test_chip_smoke_verdict_line_has_exactly_the_contract_keys():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    v = chip_smoke.verdict_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "extra": "dropped"})
    assert v == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_runs_every_leg():
    """The whole smoke at a tiny size on the CPU: every leg must pass its
    checks, and the result must still not read as a pass of the smoke."""
    out = _smoke("--rehearse-cpu", timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    report, verdict = map(json.loads, out.stdout.strip().splitlines()[-2:])
    # The verdict line is the driver's contract: exactly these keys.
    assert sorted(verdict) == ["device", "ok"]
    assert sorted(verdict["device"]) == ["count", "kind", "platform"]
    assert verdict["ok"] is False and report["rehearsal_passed"] is True
    assert verdict["device"]["platform"] == "cpu"
    summary = report
    assert sorted(summary["legs"]) == ["a", "b", "c", "d"]
    a, b = summary["legs"]["a"], summary["legs"]["b"]
    assert a["full_steps"] > 0 and a["cohort_steps"] > 0
    assert a["obliterates_applied"] > 0
    assert b["cache_files_added"] == 0
    assert b["compile"]["cache_hits"] == b["compile"]["requests"]
