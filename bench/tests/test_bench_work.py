"""The binning roofline counts the algorithm's bytes from shapes alone."""
import inspect
from pathlib import Path

import numpy as np
import pytest

import loadgen
import trace_reduce
from reference.pbs import PBSConfig, reconcile
from work import bin_xorsum

PEAKS = {"hbm_byte_per_s": 819e9}


def test_bytes_depend_only_on_the_round_shapes():
    assert list(inspect.signature(bin_xorsum.bytes_needed).parameters) == \
        ["units", "keys", "n"]
    # keys read once, a seed per unit, n bins of 33 bits per unit
    assert bin_xorsum.bytes_needed(10, 1000, 127) == 4000 + 40 + 10 * 127 * 33 / 8
    # what a kernel pads its rows to adds nothing: 1000 keys, not 1024
    assert bin_xorsum.bytes_needed(1, 1024, 127) - bin_xorsum.bytes_needed(1, 1000, 127) == 96


def test_round_one_bins_every_key_of_both_sides_once():
    rng = np.random.default_rng(3)
    a, b = loadgen.make_pair_two_sided(5000, 25, 25, rng)
    work: list = []
    res = reconcile(a, b, PBSConfig(seed=9), d_known=50, work=work)
    assert res.success and len(work) == res.rounds
    units, keys_a, keys_b, n = work[0]
    assert (keys_a, keys_b, units) == (len(a), len(b), res.g)
    assert all(w[1] < len(a) for w in work[1:])
    need = bin_xorsum.least_seconds(work, PEAKS)
    assert need == pytest.approx(sum(
        bin_xorsum.bytes_needed(u, ka, n) + bin_xorsum.bytes_needed(u, kb, n)
        for u, ka, kb, n in work) / 819e9)


def test_device_seconds_take_only_the_binning_kernel():
    trace = trace_reduce.load_json(
        Path(__file__).resolve().parent / "data" / "handoff_ops.json")
    op_s = {}
    for _, name, _, d in trace.device:
        op_s[name] = op_s.get(name, 0.0) + d / 1e9
    picked = [n for n in op_s if bin_xorsum.TRACE_NAME.search(n)]
    assert picked and all("bin_parity_xorsum_units" in n for n in picked)
    assert bin_xorsum.device_seconds(op_s) == pytest.approx(sum(op_s[n] for n in picked))
