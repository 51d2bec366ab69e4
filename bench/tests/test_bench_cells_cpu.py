"""Every cell driven end to end through the hub on the CPU (Pallas in
interpret mode) at a tiny size: traffic, metric readers and the comparison
with the reference."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import loadgen
import run
from cells import CELLS, SPEC, run_tiny, tiny

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_is_correct_and_reports_its_metrics(name, trace):
    out = run_tiny(name, trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    want = {m["name"] for m in run.cell_metrics(SPEC, name, trace)}
    if trace:
        # the CPU has no device plane: the device metrics find nothing
        want -= {m["name"] for m in SPEC["per_layer"]
                 if m["source"] == "device_trace"}
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert want <= set(out["metrics"]), (want, out["metrics"])
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 == c["limit"] for c in out["checks"].values())


def test_every_step_hands_the_program_new_pairs_of_the_same_sizes():
    """No key and no session seed repeats between steps; the sizes, d and
    A xor B under the step's mask do."""
    config, traffic = tiny("antientropy.handoff")
    w = loadgen.make_workload(config, traffic, seed=2**31 + 3)
    s0, s1 = w.step_sets(0), w.step_sets(1)
    for (a0, b0, k0), (a1, b1, k1), i in zip(s0, s1, range(len(s0))):
        assert len(a0) == len(a1) and len(b0) == len(b1)
        assert len(np.unique(a1)) == len(a1) and 0 not in a1 and 0 not in b1
        assert not np.intersect1d(np.union1d(a0, b0), np.union1d(a1, b1)).size
        assert k0["seed"] != k1["seed"]
        for step, a, b in ((0, a0, b0), (1, a1, b1)):
            truth = w.truth(loadgen.Recon(step=step, peer=i, t_due=0.0))
            assert np.array_equal(truth, np.setxor1d(a, b))
            assert len(truth) == w.d_known
    again = loadgen.make_workload(config, traffic, seed=2**31 + 3).step_sets(1)
    assert all(np.array_equal(x[0], y[0]) and x[2] == y[2] for x, y in zip(s1, again))


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for name in CELLS:
        e2e = {m["name"] for m in run.cell_metrics(SPEC, name, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(SPEC, name, True)


def test_run_exits_nonzero_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "antientropy.handoff",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout == ""
