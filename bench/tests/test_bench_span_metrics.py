"""The readers of the program's host spans: each sums its spans over the
window and divides by the reconciliations, and reports nothing where the
run holds no spans or the program has none of that name."""
import pytest

import run

SPANS = [
    ("endpoint.submit", 0.6), ("endpoint.submit", 0.5),
    ("hub.submit", 0.01), ("hub.submit", 0.02), ("hub.admit", 3.0),
    ("hub.serve", 30.0), ("hub.plan_round", 2.0), ("hub.plan_round", 0.5),
    ("store.build", 1.9), ("device.readback", 0.25), ("device.readback", 0.05),
    ("hub.collect_sketches", 0.1),
]
CASES = [
    ("peer_submit_ms_per_recon", 1100.0 / 4),
    ("admit_ms_per_recon", 3030.0 / 4),
    ("plan_ms_per_recon", 2500.0 / 4),
    ("readback_wait_ms_per_recon", 300.0 / 4),
]


def _run(spans, recons=4):
    return run.Run(steps=[], recons=[object()] * recons, window_s=60.0,
                   setup_s=40.0, correct_recons=recons, spans=spans,
                   device_trace=None, work=None, peaks=None)


@pytest.mark.parametrize("name,expect", CASES)
def test_sums_its_spans_per_reconciliation(name, expect):
    assert run.metric_reader(name)(_run(SPANS)) == pytest.approx(expect)


@pytest.mark.parametrize("name", [name for name, _ in CASES])
def test_untraced_run_reports_nothing(name):
    assert run.metric_reader(name)(_run(None)) is None


@pytest.mark.parametrize("name", [name for name, _ in CASES])
def test_program_without_the_spans_reports_nothing(name):
    """A program older than the spans (the parent of the change that added
    them) leaves the metric out of the result line, and does not raise."""
    older = [(n, d) for n, d in SPANS
             if n in ("hub.collect_sketches", "hub.encode", "store.build")]
    assert run.metric_reader(name)(_run(older)) is None
