"""The batched multi-session engine (repro.recon) vs the numpy oracle.

Every assertion is unit-for-unit equality with ``core.pbs.reconcile``: same
diff, same per-round byte ledger, same round count, same split/fake
counters — the engine is the same state machine with the bin/sketch/decode
tables computed by the accelerator kernels (DESIGN.md §5).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.bch import BCHCode, batched_decode, sketch_from_positions
from repro.core.pbs import PBSConfig, reconcile, true_diff
from repro.core.simdata import make_pair, make_pair_two_sided
from repro.kernels import bin_parity_xorsum_units
from repro.kernels import ref as kref
from repro.kernels.ops import bch_decode_batched, sketch_groups
from repro.net import AliceEndpoint, BobEndpoint, InMemoryDuplex, run_pair, tcp_loopback_pair
from repro.recon import ReconcileServer, reconcile_batch

SIZES = {5: 1500, 50: 4000, 500: 8000}


def _assert_matches_oracle(got, a, b, cfg, d_known):
    exp = reconcile(a, b, cfg, d_known=d_known)
    assert got.diff == exp.diff
    assert got.bytes_sent == exp.bytes_sent
    assert got.bytes_per_round == exp.bytes_per_round
    assert got.rounds == exp.rounds
    assert got.success == exp.success
    assert got.estimator_bytes == exp.estimator_bytes
    assert got.decode_failures == exp.decode_failures
    assert got.fake_rejections == exp.fake_rejections
    assert (got.n, got.t, got.g) == (exp.n, exp.t, exp.g)
    return exp


def test_batched_matches_oracle_across_d():
    """One mixed batch spanning d in {5, 50, 500} (several code cohorts)."""
    cases = []
    for i, d in enumerate(sorted(SIZES)):
        a, b = make_pair(SIZES[d], d, np.random.default_rng(d))
        cases.append((a, b, PBSConfig(seed=10 + i), d))
    server = ReconcileServer()
    for a, b, cfg, d in cases:
        server.submit(a, b, cfg=cfg, d_known=d)
    results = server.run()
    for i, (a, b, cfg, d) in enumerate(cases):
        exp = _assert_matches_oracle(results[i], a, b, cfg, d)
        assert exp.success and exp.diff == true_diff(a, b)


def test_estimator_and_two_sided_sessions():
    """Unknown d (ToW phase 0) and two-sided differences, batched together."""
    a1, b1 = make_pair(6000, 80, np.random.default_rng(2))
    a2, b2 = make_pair_two_sided(5000, 30, 20, np.random.default_rng(3))
    cases = [(a1, b1, PBSConfig(seed=8), None), (a2, b2, PBSConfig(seed=2), 50)]
    server = ReconcileServer()
    for a, b, cfg, dk in cases:
        server.submit(a, b, cfg=cfg, d_known=dk)
    results = server.run()
    for i, (a, b, cfg, dk) in enumerate(cases):
        exp = _assert_matches_oracle(results[i], a, b, cfg, dk)
        assert exp.success and exp.diff == true_diff(a, b)


def test_decode_failure_splits_without_perturbing_neighbors():
    """A BCH-overloaded session must 3-way split and converge while its batch
    neighbors reconcile exactly as they would alone."""
    # session 1: d=40 against t=8 in a single group -> guaranteed overload
    a_f, b_f = make_pair(5000, 40, np.random.default_rng(17))
    cfg_f = PBSConfig(seed=6, n_override=255, t_override=8, g_override=1, max_rounds=12)
    neighbors = [
        (*make_pair(2000, 10, np.random.default_rng(7)), PBSConfig(seed=21), 10),
        (*make_pair(3000, 25, np.random.default_rng(9)), PBSConfig(seed=23), 25),
    ]

    server = ReconcileServer()
    server.submit(neighbors[0][0], neighbors[0][1], cfg=neighbors[0][2], d_known=neighbors[0][3])
    server.submit(a_f, b_f, cfg=cfg_f, d_known=40)
    server.submit(neighbors[1][0], neighbors[1][1], cfg=neighbors[1][2], d_known=neighbors[1][3])
    results = server.run()

    failing = _assert_matches_oracle(results[1], a_f, b_f, cfg_f, 40)
    assert results[1].decode_failures >= 1          # the split actually fired
    assert results[1].success and results[1].diff == true_diff(a_f, b_f)
    assert failing.rounds > 1                       # re-queue spanned rounds

    # neighbors: byte-for-byte what they'd do in a batch of one
    for sid, (a, b, cfg, dk) in zip((0, 2), neighbors):
        _assert_matches_oracle(results[sid], a, b, cfg, dk)


@pytest.mark.parametrize("transport", ["memory", "loopback"])
def test_wire_endpoints_match_engine_and_oracle_across_d(transport):
    """Acceptance gate for the wire subsystem: the full multi-session grid
    (several code cohorts) with Alice and Bob as separate repro.net
    endpoints exchanging only repro.wire-encoded bytes, over both the
    in-memory duplex and the loopback socket.  Per-session results must be
    byte-identical to ``core.pbs.reconcile`` and the *measured* wire ledger
    equal to the legacy accounting for every session in the grid."""
    cases = []
    for i, d in enumerate(sorted(SIZES)):
        a, b = make_pair(SIZES[d], d, np.random.default_rng(d))
        cases.append((a, b, PBSConfig(seed=10 + i), d))

    ta, tb = (
        InMemoryDuplex.pair() if transport == "memory" else tcp_loopback_pair()
    )
    try:
        alice, bob = AliceEndpoint(ta), BobEndpoint(tb)
        for a, b, cfg, d in cases:
            alice.submit(a, cfg=cfg, d_known=d)
            bob.submit(b, cfg=cfg, d_known=d)
        results = run_pair(alice, bob)
    finally:
        ta.close()
        tb.close()

    server = ReconcileServer()
    for a, b, cfg, d in cases:
        server.submit(a, b, cfg=cfg, d_known=d)
    engine = server.run()

    for sid, (a, b, cfg, d) in enumerate(cases):
        exp = _assert_matches_oracle(results[sid], a, b, cfg, d)
        assert exp.success and exp.diff == true_diff(a, b)
        # wire ledger (measured from frames) == batched engine's accounting
        assert results[sid].bytes_per_round == engine[sid].bytes_per_round
        assert results[sid].bytes_sent == engine[sid].bytes_sent
    assert bob.verified == [True] * len(cases)


def test_session_exceeding_max_rounds_reports_failure():
    """An undersized code that can't converge must fail identically batched."""
    a, b = make_pair(2000, 30, np.random.default_rng(5))
    cfg = PBSConfig(seed=4, n_override=63, t_override=2, g_override=1, max_rounds=2)
    server = ReconcileServer()
    server.submit(a, b, cfg=cfg, d_known=30)
    got = server.run()[0]
    exp = _assert_matches_oracle(got, a, b, cfg, 30)
    assert not exp.success  # sanity: this really is the failure path


def test_reconcile_batch_convenience_order():
    pairs = [make_pair(1200, d, np.random.default_rng(40 + d)) for d in (3, 7, 11)]
    results = reconcile_batch(
        pairs, cfgs=PBSConfig(seed=5), d_knowns=[3, 7, 11]
    )
    for (a, b), res in zip(pairs, results):
        assert res.success and res.diff == true_diff(a, b)


def _assert_decode_matches_oracle(code, sketches):
    """bch_decode_batched must agree with core.bch.batched_decode row-for-row."""
    ok_ref, pos_ref = batched_decode(code, sketches)
    ok, pos, cnt = bch_decode_batched(
        jnp.asarray(sketches, dtype=jnp.int32), n=code.n, t=code.t
    )
    ok, pos, cnt = np.asarray(ok), np.asarray(pos), np.asarray(cnt)
    np.testing.assert_array_equal(ok, ok_ref)
    for u in range(len(sketches)):
        np.testing.assert_array_equal(pos[u, : cnt[u]], pos_ref[u])
        assert np.all(pos[u, cnt[u] :] == -1)  # padding convention
    return ok, pos, cnt


def test_bch_decode_batched_t1_code():
    """t=1 codes: the degenerate single-syndrome BM path, incl. the known
    2-error aliasing (two errors can mimic one; the protocol's checksum gate
    is what catches it) — kernel and numpy oracle must agree on all of it."""
    code = BCHCode(127, 1)
    sk = np.stack([
        np.zeros(1, np.int64),
        sketch_from_positions(code, np.array([13])),
        sketch_from_positions(code, np.array([5, 97])),  # aliases to one root
        sketch_from_positions(code, np.array([0])),      # boundary positions
        sketch_from_positions(code, np.array([126])),
    ])
    ok, pos, cnt = _assert_decode_matches_oracle(code, sk)
    assert ok.all()                       # t=1 decode "succeeds" on all rows
    assert list(pos[1, :1]) == [13] and list(pos[3, :1]) == [0]
    assert list(pos[4, :1]) == [126]
    assert cnt[2] == 1                    # the 2-error alias: one fake root


def test_bch_decode_batched_zero_rows_mixed_with_overload():
    """All-zero sketches (reconciled units) interleaved with genuinely
    overloaded rows (> t differing bins) in one batch: zeros decode
    trivially-ok, overloads fail and expose no positions."""
    code = BCHCode(255, 3)
    sk = np.stack([
        np.zeros(3, np.int64),
        sketch_from_positions(code, np.array([7, 19, 200])),
        sketch_from_positions(code, np.arange(1, 9)),    # 8 errors >> t=3
        np.zeros(3, np.int64),
        sketch_from_positions(code, np.arange(11, 16)),  # 5 errors > t=3
    ])
    ok, pos, cnt = _assert_decode_matches_oracle(code, sk)
    np.testing.assert_array_equal(ok, [True, True, False, True, False])
    assert cnt[0] == cnt[3] == 0 and np.all(pos[0] == -1)
    assert list(pos[1, :3]) == [7, 19, 200]
    assert cnt[2] == cnt[4] == 0 and np.all(pos[2] == -1)  # no positions leak


def test_padded_unit_decodes_trivially_ok():
    """A valid==0 row (cohort padding unit) through the full encode→decode
    path must sketch to zero and decode trivially-ok, exactly like the
    oracle decodes an all-zero difference sketch."""
    code = BCHCode(127, 2)
    rng = np.random.default_rng(42)
    U, E = 4, 64
    elems_a = rng.integers(1, 1 << 32, size=(U, E), dtype=np.uint64).astype(np.uint32)
    elems_b = elems_a.copy()
    elems_b[0, :3] = rng.integers(1, 1 << 32, size=3)   # unit 0 differs
    valid = np.ones((U, E), np.int32)
    valid[2] = 0                                         # unit 2 is all-padding
    seeds = np.full(U, 99, np.uint32)

    def sketch(elems):
        parity, _ = bin_parity_xorsum_units(
            jnp.asarray(elems), jnp.asarray(valid), jnp.asarray(seeds), n_bins=code.n
        )
        return sketch_groups(parity, code)

    diff = np.asarray(sketch(elems_a) ^ sketch(elems_b))
    assert np.all(diff[2] == 0)                          # padding sketches to zero
    ok, pos, cnt = _assert_decode_matches_oracle(code, diff.astype(np.int64))
    assert ok[2] and cnt[2] == 0 and np.all(pos[2] == -1)
    assert ok[1] and ok[3] and cnt[1] == cnt[3] == 0     # identical rows: zero diff


def test_upload_once_store_h2d_ratio():
    """The device-resident pipeline's acceptance gate: over a multi-round
    batch, total H2D traffic (store once + per-round overlays) must be at
    least 3x smaller than the re-pack-per-round layout's, with half its
    kernel launches per round."""
    server = ReconcileServer()
    for s in range(4):
        a, b = make_pair(2500, 50, np.random.default_rng(60 + s))
        server.submit(a, b, cfg=PBSConfig(seed=s), d_known=50)
    results = server.run()
    assert all(results[s].success for s in range(4))
    stats = server.stats
    assert stats["rounds"] >= 2                      # multi-round workload
    assert stats["h2d_ratio"] >= 3.0, stats
    assert stats["kernel_launches"] == 2 * stats["cohort_rounds"]
    assert stats["legacy_kernel_launches"] == 4 * stats["cohort_rounds"]
    # overlays are small: steady-state rounds ship a tiny fraction of a
    # full re-upload
    assert stats["h2d_round_bytes"] < 0.1 * stats["legacy_h2d_round_bytes"]


@pytest.mark.parametrize("n_bins", [63, 127, 8191])
def test_units_kernel_matches_mulshift_oracle(n_bins):
    """The batched bin kernel's 16-bit-split multiply-shift must equal the
    uint64 ground truth (== core.hashing.hash_to_range) bit-for-bit."""
    rng = np.random.default_rng(n_bins)
    U, E = 6, 257
    counts = rng.integers(0, E, size=U)
    counts[0], counts[1] = 0, E  # empty row + full row edges
    elems = np.zeros((U, E), np.uint32)
    valid = np.zeros((U, E), np.int32)
    for u, c in enumerate(counts):
        vals = rng.integers(1, 1 << 32, size=int(c), dtype=np.uint64).astype(np.uint32)
        elems[u, :c] = vals
        valid[u, :c] = 1
    seeds = rng.integers(0, 1 << 32, size=U, dtype=np.uint64).astype(np.uint32)

    parity, xors = bin_parity_xorsum_units(
        jnp.array(elems), jnp.array(valid), jnp.array(seeds), n_bins=n_bins
    )
    p_ref, x_ref = kref.bin_parity_xorsum_units_ref(elems, valid, seeds, n_bins)
    np.testing.assert_array_equal(np.array(parity), p_ref)
    np.testing.assert_array_equal(np.array(xors), x_ref)
