"""The idle-attribution readers of a served WU's traced stretch, on
hand-built trace records."""

import pytest

from benchmark import harness
from benchmark.kinds.bank import Run


def _run(kind="wu", trace=True, window_s=6.0, chips=1, gaps=None):
    red = None
    if trace:
        red = {"window_s": window_s, "chips": chips, "busy_s": 0.5,
               "idle_gaps": gaps if gaps is not None else [
                   ["erp:rescore-template", 4.5], ["(no span)", 0.3],
                   ["erp:result-write", 0.4], ["erp:rescore", 0.2],
                   ["erp:dispatch", 0.1]]}
    return Run(kind=kind, trace=red)


def test_rescore_share_sums_every_rescore_span():
    v = harness.load_reader("device_idle.wu_rescore").read(_run())
    assert v == pytest.approx(100 * 4.7 / 6.0)


def test_unattributed_share_is_the_no_span_idle():
    v = harness.load_reader("device_idle.wu_unattributed").read(_run())
    assert v == pytest.approx(100 * 0.3 / 6.0)


def test_shares_are_per_chip_and_read_zero_without_such_gaps():
    run = _run(chips=2, gaps=[["erp:wu", 1.2]])
    for name in ("device_idle.wu_rescore", "device_idle.wu_unattributed"):
        assert harness.load_reader(name).read(run) == 0.0
    run = _run(chips=2, gaps=[["(no span)", 3.0], ["erp:rescore", 1.2]])
    assert harness.load_reader("device_idle.wu_unattributed").read(
        run) == pytest.approx(25.0)
    assert harness.load_reader("device_idle.wu_rescore").read(
        run) == pytest.approx(10.0)


@pytest.mark.parametrize("name", ["device_idle.wu_rescore",
                                  "device_idle.wu_unattributed"])
@pytest.mark.parametrize("run", [
    _run(kind="bank"), _run(trace=False), _run(window_s=0.0), _run(chips=0),
], ids=["bank-kind", "no-trace", "empty-window", "no-chip"])
def test_no_reading_without_a_traced_chip_in_a_wu_run(name, run):
    assert harness.load_reader(name).read(run) is None
