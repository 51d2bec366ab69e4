"""The comparison that decides ``correct`` fails what it must: the control
(the reference stopped after its first round, in the program's place) at
the cells' own size, and the served path broken underneath at a tiny size:
a round that hands its learned state back unchanged, half of the peers'
results left out, and an answer altered where it is produced.  (One chip:
no exchange between chips to leave out.)"""
import copy

import numpy as np
import pytest

import check
import control
import loadgen
from cells import CELLS, run_tiny


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct_at_the_cells_size(name):
    cell = CELLS[name]
    counts = control.control_counts(
        loadgen.load_json("configs", cell["config"]),
        loadgen.load_json("traffic", cell["traffic"]), seed=2**31 + 11, steps=1)
    assert not check.correct(counts)
    assert counts["inexact"] > 0


def _state_unchanged(monkeypatch):
    import repro.net.endpoint as ep

    real = ep.apply_round_outcomes

    def apply(st, *args, **kwargs):
        before = copy.copy(st.diff)
        out = real(st, *args, **kwargs)
        st.diff = before
        return out

    monkeypatch.setattr(ep, "apply_round_outcomes", apply)


def _half_left_out(monkeypatch):
    from repro.net import AliceEndpoint

    calls = iter(range(10**6))
    real = AliceEndpoint.run

    def drop(self):
        res = real(self)
        return {} if next(calls) % 2 else res

    monkeypatch.setattr(AliceEndpoint, "run", drop)


def _answer_altered(monkeypatch):
    import repro.net.endpoint as ep

    real = ep.finalize_result

    def alter(st, plan):
        res = real(st, plan)
        res.diff = set(res.diff) ^ {1}
        return res

    monkeypatch.setattr(ep, "finalize_result", alter)


FAULTS = {"state_unchanged": _state_unchanged, "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_broken_served_path_is_not_correct(name, fault, monkeypatch):
    monkeypatch.setattr(loadgen, "DEADLINE_S", 30.0)
    FAULTS[fault](monkeypatch)
    out = run_tiny(name)
    assert out["correct"] is False, out["checks"]
    assert out["failed"] > 0


def test_only_the_diff_and_success_decide():
    """A result is held to A xor B alone: other rounds or bytes than the
    reference's are no fault, a wrong diff or a reported failure is."""
    truth = np.array([1, 5], dtype=np.uint32)
    r = loadgen.Recon(step=0, peer=0, t_due=0.0)
    r.served = (True, truth.copy(), 7, (99, 1))
    assert check.faults(r, truth) == []
    for served in [(False, truth.copy(), 2, (10, 4)),
                   (True, np.array([1, 6], dtype=np.uint32), 2, (10, 4)),
                   (True, truth[:1], 2, (10, 4))]:
        r.served = served
        assert check.faults(r, truth) == ["inexact"]
    r.served = None
    assert check.faults(r, truth) == ["unfinished"]
