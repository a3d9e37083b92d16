"""Watchdog supervision: deadline registry, escalation ladder, lease
self-fencing, incident log + poison-range quarantine accounting, and the
supervised-restart loop (runtime/watchdog.py, runtime/supervise.py)."""

import json
import sys
import time

import pytest

from boinc_app_eah_brp_tpu.runtime import supervise, watchdog
from boinc_app_eah_brp_tpu.runtime.errors import RADPUL_TEMPORARY_EXIT


@pytest.fixture(autouse=True)
def exits(monkeypatch):
    """Capture hard exits instead of dying, scrub watchdog env, and leave
    the module disarmed for the next test."""
    captured = []
    monkeypatch.setattr(watchdog, "_exit_fn", captured.append)
    for var in (
        watchdog.ENV_ENABLE,
        watchdog.ENV_SPEC,
        watchdog.ENV_GRACE,
        watchdog.ENV_POLL,
        watchdog.ENV_QUARANTINE_K,
        watchdog.ENV_INCIDENT_LOG,
    ):
        monkeypatch.delenv(var, raising=False)
    yield captured
    watchdog.disarm()


def _wait_for(pred, timeout_s=8.0):
    deadline = time.monotonic() + timeout_s
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.02)
    return pred()


# ---------------------------------------------------------------------------
# deadline registry


def test_parse_spec_overrides_and_star():
    d = watchdog._parse_spec("dispatch=2,lease_io=1.5")
    assert d["dispatch"] == 2.0
    assert d["lease_io"] == 1.5
    assert d["drain"] == watchdog.DEADLINES["drain"]  # untouched stages keep defaults
    d = watchdog._parse_spec("*=5,merge=9")
    assert set(d.values()) == {5.0, 9.0} and d["merge"] == 9.0


@pytest.mark.parametrize(
    "bad", ["bogus_stage=3", "dispatch", "dispatch=fast", "dispatch=0", "merge=-1"]
)
def test_parse_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        watchdog._parse_spec(bad)


def test_serving_stages_registered():
    """The serving tier's dispatch/result stages are first-class
    watchdog deadlines: registered defaults, spec-overridable (the
    chaos soak pins serving_dispatch=2), and visible through
    beat_ages() for /statusz."""
    assert watchdog.DEADLINES["serving_dispatch"] > 0
    assert watchdog.DEADLINES["serving_result"] > 0
    d = watchdog._parse_spec("serving_dispatch=2,serving_result=30")
    assert d["serving_dispatch"] == 2.0 and d["serving_result"] == 30.0


def test_beat_ages_reports_armed_stages(monkeypatch):
    assert watchdog.beat_ages() == {}  # unarmed: nothing to report
    monkeypatch.setenv(watchdog.ENV_SPEC, "*=60")
    assert watchdog.arm() is True
    with watchdog.guard("serving_dispatch", ticket="t-wu-1"):
        time.sleep(0.05)
        ages = watchdog.beat_ages()
        assert set(ages) == {"serving_dispatch"}
        assert 0.0 <= ages["serving_dispatch"] < 5.0
        watchdog.beat("serving_dispatch")
        assert watchdog.beat_ages()["serving_dispatch"] <= ages[
            "serving_dispatch"
        ] + 0.05
    assert watchdog.beat_ages() == {}  # guard exit clears the entry


def test_env_off_keeps_watchdog_inert(monkeypatch):
    monkeypatch.setenv(watchdog.ENV_ENABLE, "off")
    assert watchdog.arm() is False
    assert not watchdog.armed()
    with watchdog.guard("dispatch"):
        assert watchdog._entries == {}


def test_unarmed_guard_registers_nothing():
    with watchdog.guard("dispatch"):
        assert watchdog._entries == {}


# ---------------------------------------------------------------------------
# escalation ladder


def test_breach_escalates_to_hard_exit(monkeypatch, tmp_path, exits):
    monkeypatch.setenv(watchdog.ENV_SPEC, "*=0.15")
    monkeypatch.setenv(watchdog.ENV_GRACE, "0.3")
    monkeypatch.setenv(watchdog.ENV_POLL, "0.05")
    log = watchdog.IncidentLog(str(tmp_path / "inc.json"))
    assert watchdog.arm(incident_log=log) is True
    with watchdog.guard("dispatch", start=8, stop=12):
        assert _wait_for(lambda: bool(exits))
    assert exits[0] == RADPUL_TEMPORARY_EXIT
    assert watchdog.abort_requested()
    doc = log.read()
    assert watchdog.validate_incident_log(doc) == []
    assert doc["incidents"][0]["stage"] == "dispatch"
    assert doc["incidents"][0]["reason"] == "watchdog:dispatch"
    assert doc["incidents"][0]["window"] == [8, 12]


def test_breach_recovering_within_grace_avoids_exit(monkeypatch, exits):
    monkeypatch.setenv(watchdog.ENV_SPEC, "*=0.15")
    monkeypatch.setenv(watchdog.ENV_GRACE, "30")
    monkeypatch.setenv(watchdog.ENV_POLL, "0.05")
    assert watchdog.arm() is True
    with watchdog.guard("drain"):
        assert _wait_for(watchdog.abort_requested)  # breached (ladder ran) ...
    time.sleep(0.2)
    assert not exits  # ... but completion inside the grace window spared the rc-99


def test_beat_defers_the_deadline(monkeypatch, exits):
    monkeypatch.setenv(watchdog.ENV_SPEC, "*=0.4")
    monkeypatch.setenv(watchdog.ENV_POLL, "0.05")
    assert watchdog.arm() is True
    with watchdog.guard("rescore_feed"):
        for _ in range(6):  # 0.6 s total, but never 0.4 s without progress
            time.sleep(0.1)
            watchdog.beat("rescore_feed")
    assert not exits
    assert not watchdog.abort_requested()


def test_lease_breach_self_fences_and_claims_refuse(monkeypatch, tmp_path, exits):
    monkeypatch.setenv(watchdog.ENV_SPEC, "lease_io=0.1")
    monkeypatch.setenv(watchdog.ENV_GRACE, "30")
    monkeypatch.setenv(watchdog.ENV_POLL, "0.05")
    assert watchdog.arm() is True
    assert not watchdog.fenced()
    with watchdog.guard("lease_io", op="heartbeat"):
        assert _wait_for(watchdog.fenced)
    from boinc_app_eah_brp_tpu.runtime.resilience import LeaseBoard

    board = LeaseBoard(str(tmp_path), "h0")
    assert board.try_claim(0, 0, 8) is None  # fenced host takes no shards
    assert not exits
    # a fresh run in the same process starts healthy again
    assert watchdog.arm() is True
    assert not watchdog.fenced() and not watchdog.abort_requested()
    assert board.try_claim(0, 0, 8) is not None


# ---------------------------------------------------------------------------
# incident log + quarantine accounting


def test_incident_log_roundtrip_counts_and_quarantine(tmp_path):
    log = watchdog.IncidentLog(str(tmp_path / "i.json"))
    for _ in range(3):
        log.append(stage="dispatch", reason="watchdog:dispatch", window=(8, 12))
    log.append(stage="merge", reason="watchdog:merge", window=(20, 24))
    log.append(stage="crash", reason="signal-9", window=None)
    counts = log.window_counts()
    assert counts == {(8, 12): 3, (20, 24): 1}
    assert log.quarantined(k=3) == [(8, 12)]
    assert log.quarantined(k=1) == [(8, 12), (20, 24)]
    assert log.quarantined(k=4) == []
    assert watchdog.validate_incident_log(log.read()) == []


def test_quarantine_merges_adjacent_windows(tmp_path):
    log = watchdog.IncidentLog(str(tmp_path / "i.json"))
    for w in ((8, 12), (12, 16)):
        log.append(stage="dispatch", reason="watchdog:dispatch", window=w)
        log.append(stage="dispatch", reason="watchdog:dispatch", window=w)
    assert log.quarantined(k=2) == [(8, 16)]


def test_quarantine_threshold_env(monkeypatch):
    assert watchdog.quarantine_threshold() == 3
    monkeypatch.setenv(watchdog.ENV_QUARANTINE_K, "2")
    assert watchdog.quarantine_threshold() == 2
    monkeypatch.setenv(watchdog.ENV_QUARANTINE_K, "0")
    assert watchdog.quarantine_threshold() == 1  # floor: 0 would quarantine all
    monkeypatch.setenv(watchdog.ENV_QUARANTINE_K, "many")
    assert watchdog.quarantine_threshold() == 3


def test_incident_log_survives_torn_write(tmp_path):
    path = tmp_path / "i.json"
    path.write_text("{torn", encoding="utf-8")
    log = watchdog.IncidentLog(str(path))
    assert log.read()["incidents"] == []
    log.append(stage="dispatch", reason="watchdog:dispatch", window=(0, 4))
    assert log.window_counts() == {(0, 4): 1}


def test_default_incident_path(monkeypatch):
    assert watchdog.default_incident_path("/w/ckpt.cpt") == "/w/ckpt.cpt.incidents.json"
    assert watchdog.default_incident_path(None) is None
    monkeypatch.setenv(watchdog.ENV_INCIDENT_LOG, "/elsewhere/log.json")
    assert watchdog.default_incident_path("/w/ckpt.cpt") == "/elsewhere/log.json"


def test_on_crash_dump_skips_watchdog_and_temporary_exit_reasons(
    tmp_path, monkeypatch
):
    log = watchdog.IncidentLog(str(tmp_path / "i.json"))
    monkeypatch.setattr(watchdog, "_incident_log", log)
    watchdog.on_crash_dump("watchdog:dispatch")  # already appended by _escalate
    watchdog.on_crash_dump(f"exit-code-{RADPUL_TEMPORARY_EXIT}")  # same wedge
    assert log.read()["incidents"] == []
    watchdog.on_crash_dump("signal-15")
    assert [r["reason"] for r in log.read()["incidents"]] == ["signal-15"]


def test_runnable_segments_complement():
    assert watchdog.runnable_segments(10, []) == [(0, 10)]
    assert watchdog.runnable_segments(10, [(4, 6)]) == [(0, 4), (6, 10)]
    assert watchdog.runnable_segments(10, [(0, 4)]) == [(4, 10)]
    assert watchdog.runnable_segments(10, [(8, 40)]) == [(0, 8)]
    assert watchdog.runnable_segments(10, [(2, 4), (4, 8)]) == [(0, 2), (8, 10)]
    assert watchdog.runnable_segments(10, [(4, 6)], start=5) == [(6, 10)]
    assert watchdog.runnable_segments(10, [(4, 6)], start=7) == [(7, 10)]
    assert watchdog.runnable_segments(4, [(0, 4)]) == []


def test_validate_incident_log_flags_problems():
    assert watchdog.validate_incident_log([]) == ["incident log is not a JSON object"]
    p = watchdog.validate_incident_log({"schema": "nope", "incidents": 3})
    assert any("schema" in m for m in p) and any("not a list" in m for m in p)
    p = watchdog.validate_incident_log(
        {"schema": watchdog.INCIDENT_SCHEMA, "incidents": [{"t": 1.0}]}
    )
    assert any("missing 'pid'" in m for m in p)
    bad_window = {
        "t": 1.0, "pid": 2, "stage": "dispatch", "reason": "r", "window": [4, 4],
    }
    p = watchdog.validate_incident_log(
        {"schema": watchdog.INCIDENT_SCHEMA, "incidents": [bad_window]}
    )
    assert any("window" in m for m in p)


# ---------------------------------------------------------------------------
# supervised-restart loop


def test_should_restart_policy():
    assert supervise.should_restart(RADPUL_TEMPORARY_EXIT) is True
    assert supervise.should_restart(0) is False
    assert supervise.should_restart(3) is False  # mapped RADPUL_* rc is final
    assert supervise.should_restart(-9) is False  # signal death needs the opt-in
    assert supervise.should_restart(-9, restart_on_crash=True) is True


def test_run_supervised_restarts_until_clean(monkeypatch):
    monkeypatch.setenv(supervise.ENV_BACKOFF, "0.5")
    rcs = iter([RADPUL_TEMPORARY_EXIT, RADPUL_TEMPORARY_EXIT, 0])
    passes, naps = [], []

    def runner(cmd, env):
        passes.append(list(cmd))
        return next(rcs)

    rc = supervise.run_supervised(
        ["worker", "-i", "wu"], max_restarts=5, runner=runner, sleep=naps.append
    )
    assert rc == 0
    assert len(passes) == 3 and all(p == ["worker", "-i", "wu"] for p in passes)
    assert naps == [0.5, 1.0]  # exponential backoff from the env base


def test_run_supervised_budget_exhausted_returns_last_rc(monkeypatch):
    monkeypatch.setenv(supervise.ENV_BACKOFF, "0")
    passes = []

    def runner(cmd, env):
        passes.append(1)
        return RADPUL_TEMPORARY_EXIT

    rc = supervise.run_supervised(
        ["w"], max_restarts=2, runner=runner, sleep=lambda s: None
    )
    assert rc == RADPUL_TEMPORARY_EXIT
    assert len(passes) == 3  # first pass + 2 restarts, then give up


def test_run_supervised_crash_restart_needs_optin(monkeypatch):
    monkeypatch.setenv(supervise.ENV_BACKOFF, "0")
    rc = supervise.run_supervised(["w"], runner=lambda c, e: -9, sleep=lambda s: None)
    assert rc == -9
    rcs = iter([-9, 0])
    rc = supervise.run_supervised(
        ["w"], restart_on_crash=True, runner=lambda c, e: next(rcs),
        sleep=lambda s: None,
    )
    assert rc == 0


def test_strip_supervised_flag():
    strip = supervise.strip_supervised_flag
    assert strip(["-i", "x"]) == (["-i", "x"], None)
    assert strip(["--supervised", "3", "-i", "x"]) == (["-i", "x"], 3)
    assert strip(["-i", "x", "--supervised"]) == (
        ["-i", "x"], supervise.DEFAULT_MAX_RESTARTS,
    )
    assert strip(["--supervised", "-i", "x"]) == (
        ["-i", "x"], supervise.DEFAULT_MAX_RESTARTS,
    )


def test_supervised_parent_stays_off_the_device(tmp_path):
    """The ``--supervised`` parent may import JAX but must never start a
    backend: it would hold the chip, and the worker it re-execs would then
    fail or hang on it.  The real parent runs one worker pass (a worker
    that exits on a missing input) and then reports its backend state."""
    import os
    import subprocess

    code = (
        "import sys\n"
        "from boinc_app_eah_brp_tpu.runtime import cli\n"
        "rc = cli.main(['--supervised', '0', '-i', sys.argv[1], '-o', "
        "sys.argv[2], '-t', sys.argv[1]])\n"
        "from jax._src import xla_bridge\n"
        "print('RC', rc, 'BACKEND', xla_bridge.backends_are_initialized())\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "absent.bin4"),
         str(tmp_path / "out.cand")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, ERP_SUPERVISE_BACKOFF_S="0"),
    )
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("RC ") and not last.startswith("RC 0 "), r.stderr
    assert last.endswith("BACKEND False"), last


def test_self_cmd_reexecs_this_package():
    cmd = supervise.self_cmd(["-i", "wu", "-o", "out"])
    assert cmd[0] == sys.executable
    assert cmd[1:3] == ["-m", "boinc_app_eah_brp_tpu"]
    assert cmd[3:] == ["-i", "wu", "-o", "out"]


def test_incident_log_append_is_atomic_json(tmp_path):
    """The sidecar on disk is always a complete erp-incident-log/1 doc
    (atomic replace), so a crash mid-append can't poison recovery."""
    log = watchdog.IncidentLog(str(tmp_path / "i.json"))
    for i in range(5):
        log.append(stage="dispatch", reason="watchdog:dispatch", window=(i, i + 1))
        doc = json.loads((tmp_path / "i.json").read_text(encoding="utf-8"))
        assert doc["schema"] == watchdog.INCIDENT_SCHEMA
        assert len(doc["incidents"]) == i + 1
