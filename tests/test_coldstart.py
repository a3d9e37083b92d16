"""Fresh-container cold start (VERDICT r04 #9): bench and the chip smoke
build the native median themselves and refuse the silent device-median
fallback — a fresh container without ``native/build`` would otherwise pin
the ~47 s/pass device median."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_tree(tmp_path, with_sources=True):
    """A minimal repo skeleton simulating a fresh container: native
    sources present (git-tracked), native/build absent (not tracked)."""
    root = tmp_path / "fresh"
    root.mkdir()
    if with_sources:
        shutil.copytree(
            os.path.join(REPO, "native"),
            root / "native",
            ignore=shutil.ignore_patterns("build"),
        )
    return root


def _run(code, env_extra):
    env = dict(os.environ, **env_extra)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        cwd=REPO,
        timeout=600,
    )


def test_cold_start_builds_and_loads_native(tmp_path):
    """ensure_native on a build-less tree runs make and the re-probe
    picks the fresh library up (exclusive $ERP_RNGMED_LIB pins the probe
    to the fresh tree, not this checkout's build)."""
    root = _fresh_tree(tmp_path)
    lib = root / "native" / "build" / "liberp_rngmed.so"
    assert not lib.exists()
    r = _run(
        f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
        "import bench\n"
        f"ok = bench.ensure_native(repo={str(root)!r})\n"
        "assert ok, 'build-and-reprobe must succeed'\n"
        "from boinc_app_eah_brp_tpu.ops.native_median import native_available\n"
        "assert native_available()\n"
        "from boinc_app_eah_brp_tpu.ops.native_median import running_median_native\n"
        "import numpy as np\n"
        "out = running_median_native(np.arange(32, dtype=np.float32), 5)\n"
        "assert out.shape == (28,)\n"
        "print('COLD START OK')",
        {"ERP_RNGMED_LIB": str(lib)},
    )
    assert r.returncode == 0, r.stderr
    assert "COLD START OK" in r.stdout
    assert lib.exists()


def test_rebuild_replaces_a_copied_in_library(tmp_path):
    """``rebuild=True`` (the chip smoke's build phase) runs ``make -B``: a
    library that came with the tree is rebuilt from the tracked sources
    even when it is newer than them."""
    root = _fresh_tree(tmp_path)
    lib = root / "native" / "build" / "liberp_rngmed.so"
    lib.parent.mkdir()
    lib.write_bytes(b"not a shared object")
    r = _run(
        f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
        "import bench\n"
        f"assert bench.ensure_native(repo={str(root)!r}, rebuild=True)\n"
        "print('REBUILT')",
        {"ERP_RNGMED_LIB": str(lib)},
    )
    assert r.returncode == 0, r.stderr
    assert "REBUILT" in r.stdout
    assert lib.read_bytes()[:4] == b"\x7fELF"


def test_cold_start_refuses_degraded_path(tmp_path):
    """No sources, no library: bench refuses unless the operator
    explicitly accepts the device median."""
    root = _fresh_tree(tmp_path, with_sources=False)
    lib = root / "nonexistent.so"
    code = (
        f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
        "import bench\n"
        f"print('RET', bench.ensure_native(repo={str(root)!r}))"
    )
    r = _run(code, {"ERP_RNGMED_LIB": str(lib)})
    assert r.returncode != 0
    assert "refusing" in (r.stderr + r.stdout)
    # explicit override: degraded path accepted, returns False
    r2 = _run(code, {"ERP_RNGMED_LIB": str(lib), "ERP_ALLOW_DEVICE_MEDIAN": "1"})
    assert r2.returncode == 0, r2.stderr
    assert "RET False" in r2.stdout


def test_explicit_device_median_also_guarded(tmp_path):
    """ERP_MEDIAN=device degrades bench exactly like a missing library
    and must trip the same refusal (a stray exported A/B knob cannot
    burn a chip window); ERP_ALLOW_DEVICE_MEDIAN=1 overrides."""
    code = (
        f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
        "import bench\n"
        "print('RET', bench.ensure_native())"
    )
    r = _run(code, {"ERP_MEDIAN": "device"})
    assert r.returncode != 0
    assert "ERP_MEDIAN=device" in (r.stderr + r.stdout)
    r2 = _run(code, {"ERP_MEDIAN": "device", "ERP_ALLOW_DEVICE_MEDIAN": "1"})
    assert r2.returncode == 0, r2.stderr
    assert "RET False" in r2.stdout


def test_rngmed_env_path_is_exclusive(tmp_path):
    """$ERP_RNGMED_LIB pointing at a missing file must NOT fall back to
    the repo build: an explicitly named path that fails stays failed."""
    r = _run(
        f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
        "from boinc_app_eah_brp_tpu.ops.native_median import native_available\n"
        "print('AVAIL', native_available())",
        {"ERP_RNGMED_LIB": str(tmp_path / "missing.so")},
    )
    assert r.returncode == 0, r.stderr
    assert "AVAIL False" in r.stdout
