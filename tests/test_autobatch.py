"""Batch auto-selection (runtime/autobatch.py)."""

import json

import numpy as np
import pytest

from boinc_app_eah_brp_tpu.runtime import autobatch


NSAMPLES = 12_582_912  # production padded length


def test_env_override(monkeypatch):
    monkeypatch.setenv("ERP_BATCH", "24")
    assert autobatch.choose_batch(NSAMPLES) == 24


def test_model_batch_scales_with_budget():
    per = autobatch._WORKING_SET_FACTOR * NSAMPLES * 4.0
    assert autobatch.model_batch(NSAMPLES, None) == 16  # unknown budget
    assert autobatch.model_batch(NSAMPLES, int(per * 20)) == 8
    assert autobatch.model_batch(NSAMPLES, int(per * 120)) == 64
    assert autobatch.model_batch(NSAMPLES, int(per * 10_000)) == 128  # clamp


def test_sweep_overrules_model_when_budget_unknown(tmp_path, monkeypatch):
    sweep = tmp_path / "BATCHSWEEP_r99.json"
    sweep.write_text(json.dumps({"best_batch": 64}))
    monkeypatch.setenv("ERP_BATCH_SWEEP", str(sweep))
    monkeypatch.delenv("ERP_BATCH", raising=False)
    monkeypatch.setattr(autobatch, "device_memory_budget", lambda: None)
    assert autobatch.choose_batch(NSAMPLES) == 64


def test_known_budget_caps_sweep(tmp_path, monkeypatch):
    sweep = tmp_path / "BATCHSWEEP_r99.json"
    sweep.write_text(json.dumps({"best_batch": 128}))
    monkeypatch.setenv("ERP_BATCH_SWEEP", str(sweep))
    monkeypatch.delenv("ERP_BATCH", raising=False)
    per = autobatch._WORKING_SET_FACTOR * NSAMPLES * 4.0
    monkeypatch.setattr(
        autobatch, "device_memory_budget", lambda: int(per * 30)
    )
    # sweep's 128 exceeds what ~30 templates of budget supports -> model
    assert autobatch.choose_batch(NSAMPLES) == 16


def test_unreadable_sweep_falls_through(tmp_path, monkeypatch):
    sweep = tmp_path / "broken.json"
    sweep.write_text("{not json")
    monkeypatch.setenv("ERP_BATCH_SWEEP", str(sweep))
    monkeypatch.delenv("ERP_BATCH", raising=False)
    monkeypatch.setattr(autobatch, "device_memory_budget", lambda: None)
    assert autobatch.choose_batch(NSAMPLES) == 16


def test_model_batch_within_compiler_proven_bound():
    """The v5e model choice stays within the AOT-proven feasibility edge
    (a deviceless v5e compile of the production step fit batch 64 in the
    15.75 GB chip, and not 72+); the anchored factor must not pick an
    infeasible batch nor collapse below the useful range."""
    b = autobatch.model_batch(3 * (1 << 22), int(15.75e9))
    assert 16 <= b <= 64


def test_sweep_accepted_on_same_device_kind_and_nsamples(tmp_path, monkeypatch):
    """A sweep rung measured on THIS device kind AT this problem size is
    the strongest feasibility proof and is accepted without a model gate:
    the AOT-proven batch 64 on v5e must be used even though the model
    alone would pick 32 (per-template HBM is not linear in batch, so no
    factor-based check can arbitrate)."""
    import json

    n = 3 * (1 << 22)
    sweep = tmp_path / "BATCHSWEEP_r99.json"
    sweep.write_text(
        json.dumps(
            {"best_batch": 64, "device_kind": "TPU v5 lite", "nsamples": n}
        )
    )
    monkeypatch.setenv("ERP_BATCH_SWEEP", str(sweep))
    monkeypatch.delenv("ERP_BATCH", raising=False)
    monkeypatch.setattr(
        autobatch, "device_memory_budget", lambda: int(15.0e9)
    )
    monkeypatch.setattr(
        autobatch, "_current_device_kind", lambda: "TPU v5 lite"
    )
    assert autobatch.choose_batch(n) == 64
    assert autobatch.model_batch(n, int(15.75e9)) == 32


def test_sweep_nsamples_mismatch_falls_back_to_model(tmp_path, monkeypatch):
    """Same chip but a different problem size: a rung proven at 2^20
    samples says nothing about a 3*2^22 WU's HBM footprint, so the rung
    must pass the memory-model gate instead of unguarded acceptance."""
    import json

    n = 3 * (1 << 22)
    sweep = tmp_path / "BATCHSWEEP_r99.json"
    sweep.write_text(
        json.dumps(
            {
                "best_batch": 64,
                "device_kind": "TPU v5 lite",
                "nsamples": 1 << 20,
            }
        )
    )
    monkeypatch.setenv("ERP_BATCH_SWEEP", str(sweep))
    monkeypatch.delenv("ERP_BATCH", raising=False)
    monkeypatch.setattr(
        autobatch, "device_memory_budget", lambda: int(15.0e9)
    )
    monkeypatch.setattr(
        autobatch, "_current_device_kind", lambda: "TPU v5 lite"
    )
    # 64 fails the model gate (fit is 32 at this budget) -> model choice
    assert autobatch.choose_batch(n) == 32


def test_sweep_missing_nsamples_uses_model_gate(tmp_path, monkeypatch):
    """A legacy artifact without nsamples can't prove the problem size:
    acceptance goes through the model gate — a rung within the model fit
    is still taken, one beyond it is not."""
    import json

    n = 3 * (1 << 22)
    sweep = tmp_path / "BATCHSWEEP_r99.json"
    sweep.write_text(
        json.dumps({"best_batch": 16, "device_kind": "TPU v5 lite"})
    )
    monkeypatch.setenv("ERP_BATCH_SWEEP", str(sweep))
    monkeypatch.delenv("ERP_BATCH", raising=False)
    monkeypatch.setattr(
        autobatch, "device_memory_budget", lambda: int(15.0e9)
    )
    monkeypatch.setattr(
        autobatch, "_current_device_kind", lambda: "TPU v5 lite"
    )
    # 16 <= model fit 32 -> accepted through the gate
    assert autobatch.choose_batch(n) == 16


def test_sweep_rejected_on_different_device_kind(tmp_path, monkeypatch):
    """A sweep from another chip class falls back to the model: its
    rungs prove nothing about this device's HBM."""
    import json

    sweep = tmp_path / "BATCHSWEEP_r99.json"
    sweep.write_text(
        json.dumps({"best_batch": 128, "device_kind": "TPU v5p"})
    )
    monkeypatch.setenv("ERP_BATCH_SWEEP", str(sweep))
    monkeypatch.delenv("ERP_BATCH", raising=False)
    n = 3 * (1 << 22)
    monkeypatch.setattr(
        autobatch, "device_memory_budget", lambda: int(15.75e9)
    )
    monkeypatch.setattr(
        autobatch, "_current_device_kind", lambda: "TPU v5 lite"
    )
    assert autobatch.choose_batch(n) == 32  # the model's v5e choice


def test_sweep_artifact_round_ordering(tmp_path, monkeypatch):
    """BATCHSWEEP_r10 outranks BATCHSWEEP_r9 (parsed round number, not
    lexicographic — the ADVICE r04 artifact-ordering class)."""
    import json

    repo_like = tmp_path
    (repo_like / "BATCHSWEEP_r9.json").write_text(
        json.dumps({"best_batch": 16}))
    (repo_like / "BATCHSWEEP_r10.json").write_text(
        json.dumps({"best_batch": 64}))
    monkeypatch.delenv("ERP_BATCH_SWEEP", raising=False)
    import glob as glob_mod

    real_glob = glob_mod.glob
    monkeypatch.setattr(
        autobatch.glob, "glob",
        lambda pat: real_glob(str(repo_like / "BATCHSWEEP_r*.json")),
    )
    got = autobatch._sweep_best_batch()
    assert got is not None and got[0] == 64
