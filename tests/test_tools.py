"""Packaging/harness tools: app_info generation, bench harness wiring,
compilation-cache env hook (SURVEY.md section 2.6)."""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_make_app_info_valid_xml(tmp_path):
    out = tmp_path / "app_info.xml"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "make_app_info.py"),
         "-o", str(out)],
        capture_output=True,
    )
    assert r.returncode == 0, r.stderr
    root = ET.parse(out).getroot()
    assert root.tag == "app_info"
    # same anonymous-platform schema as the reference app_info.xml.in
    assert root.find("app/name").text == "einsteinbinary_BRP4"
    av = root.find("app_version")
    assert av.find("app_name").text == "einsteinbinary_BRP4"
    assert int(av.find("version_num").text) == 56
    assert av.find("file_ref/main_program") is not None


def test_bench_single_requires_testwu(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_single.py"),
         "--testwu", str(tmp_path)],
        capture_output=True,
    )
    assert r.returncode == 1
    assert b"missing" in r.stderr


def test_runall_fraction_parser(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import runall

    p = tmp_path / "shmem"
    p.write_bytes(b"<app>\n<fraction_done>0.4375</fraction_done>\n</app>\x00")
    assert runall.read_fraction(str(p)) == "0.4375"
    assert runall.read_fraction(str(tmp_path / "nope")) == "-"


@pytest.mark.parametrize("where", ["off", "env", "default"])
def test_compilation_cache_hook(tmp_path, monkeypatch, where):
    """``off`` leaves the jax config alone; a set
    ``JAX_COMPILATION_CACHE_DIR`` is JAX's own to read, so no directory is
    set in code; otherwise the cache lands on the fixed in-checkout path."""
    import jax

    from boinc_app_eah_brp_tpu.runtime.driver import (
        compilation_cache_dir,
        default_cache_dir,
        enable_compilation_cache,
    )

    assert default_cache_dir() == os.path.join(REPO, ".erp_cache", "xla")
    saved_dir = jax.config.jax_compilation_cache_dir
    saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
    sentinel = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    try:
        monkeypatch.delenv("ERP_COMPILATION_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        if where == "off":
            monkeypatch.setenv("ERP_COMPILATION_CACHE", "off")
            want_dir, want_cfg = None, sentinel
        elif where == "env":
            env_dir = str(tmp_path / "from-env")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            want_dir, want_cfg = env_dir, sentinel
        else:
            want_dir = want_cfg = default_cache_dir()
        assert compilation_cache_dir() == want_dir
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == want_cfg
        if where == "default":
            assert os.path.isdir(want_dir)
    finally:
        # restore so later >1s compiles in this process don't write into
        # a removed directory
        jax.config.update("jax_compilation_cache_dir", saved_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved_min)


def test_make_bundle_produces_installable_dir(tmp_path):
    """One command -> a directory a BOINC client can register: wrapper as
    main program, worker zipapp + native median as bundled files, install
    script, README (debian/rules:196-206 analogue)."""
    out = tmp_path / "bundle"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "make_bundle.py"),
         "--out", str(out)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    for name in ("erp_wrapper", "liberp_rngmed.so", "eah_brp_worker.pyz",
                 "app_info.xml", "install.sh", "README.md"):
        assert (out / name).exists(), name
    assert os.access(out / "install.sh", os.X_OK)

    root = ET.parse(out / "app_info.xml").getroot()
    refs = [fr.find("file_name").text
            for fr in root.findall("app_version/file_ref")]
    assert refs == ["erp_wrapper", "eah_brp_worker.pyz", "liberp_rngmed.so"]
    names = [fi.find("name").text for fi in root.findall("file_info")]
    assert set(refs) == set(names)
    main_ref = root.find("app_version/file_ref")
    assert main_ref.find("main_program") is not None
    assert "--stderr-file" in root.find("app_version/cmdline").text

    # the zipapp answers the CLI surface without unpacking (usage text on
    # missing args; the full search path is covered by the CLI tests)
    rr = subprocess.run(
        ["python3", str(out / "eah_brp_worker.pyz"), "-h"],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert "--create-wisdom" not in rr.stderr  # help is the driver's
    assert "input_file" in rr.stdout + rr.stderr


def test_debug_log_routing(capsys):
    """route_debug_to_stderr flips ONLY the DEBUG stream: bench's stdout
    is a machine-read one-JSON-line channel, and the worker logger's
    default debug-to-stdout (the reference's semantics) broke it."""
    sys.path.insert(0, REPO)
    from boinc_app_eah_brp_tpu.runtime import logging as erplog

    try:
        erplog.debug("to stdout\n")
        out = capsys.readouterr()
        assert "to stdout" in out.out and "to stdout" not in out.err
        erplog.route_debug_to_stderr()
        erplog.debug("to stderr\n")
        erplog.info("info stays on stderr\n")
        out = capsys.readouterr()
        assert out.out == ""
        assert "to stderr" in out.err and "info stays" in out.err
    finally:
        erplog.route_debug_to_stderr(False)


def _fixture_report(templates=6662, wall=120.0, stall=4.0, ckpts=3):
    """A schema-valid run report built through the real metrics layer
    (force-enabled in-memory window), so the fixture can never drift from
    the producer."""
    from boinc_app_eah_brp_tpu.runtime import metrics

    assert metrics.configure(force=True)
    try:
        metrics.counter("search.templates").inc(templates)
        metrics.counter("search.drain_stall_s", unit="s").inc(stall)
        metrics.counter("checkpoint.count").inc(ckpts)
        metrics.gauge("search.batch_size").set(64)
        h = metrics.histogram(
            "search.lookahead_occupancy", metrics.OCCUPANCY_BUCKETS
        )
        for v in (1, 2, 2, 1):
            h.observe(v)
        metrics.record_phase("template loop", wall)
    finally:
        report = metrics.finish(0)
    report["wall_s"] = wall  # deterministic fixture wall
    return report


def test_metrics_report_render_stream_and_report(tmp_path):
    """tools/metrics_report.py renders both artifact forms (JSONL stream
    and run-report JSON) into a human table."""
    import json

    report = _fixture_report()
    rpt_path = tmp_path / "run.report.json"
    rpt_path.write_text(json.dumps(report))
    stream_path = tmp_path / "run.jsonl"
    stream_path.write_text(
        json.dumps({"kind": "start", "schema": "erp-metrics/1", "t": 0})
        + "\n"
        + json.dumps({"kind": "heartbeat", "t": 1, "seq": 1,
                      "metrics": report["metrics"]})
        + "\n"
        + json.dumps({"kind": "run_report", "t": 2, "report": report})
        + "\n"
    )
    for path in (rpt_path, stream_path):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
             str(path)],
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr
        assert "search.templates" in r.stdout
        assert "template loop" in r.stdout
        assert "search.lookahead_occupancy" in r.stdout
        assert "exit_status=0" in r.stdout


def test_metrics_report_diff(tmp_path):
    import json

    a = _fixture_report(templates=6662, wall=120.0)
    b = _fixture_report(templates=6662, wall=96.0)
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         "--diff", str(pa), str(pb)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    assert "wall_s" in r.stdout
    assert "-20.0%" in r.stdout  # 120 -> 96


def test_metrics_report_check(tmp_path):
    """--check is the bench-pipeline gate: exit 0 on a schema-valid
    report, exit 1 (naming the problems) on a broken one."""
    import json

    good = tmp_path / "good.json"
    good.write_text(json.dumps(_fixture_report()))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         "--check", str(good)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout

    broken = _fixture_report()
    broken["metrics"]["histograms"]["search.lookahead_occupancy"][
        "counts"
    ] = [1]  # wrong length vs buckets
    del broken["wall_s"]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(broken))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         "--check", str(bad)],
        capture_output=True, text=True,
    )
    assert r.returncode == 1
    assert "INVALID" in r.stdout
    assert "wall_s" in r.stdout
