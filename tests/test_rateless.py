"""Rateless recovery acceptance (DESIGN.md §16, the MSG_PARITY ladder).

The algebraic foundation: the 2t-syndrome vector of an (n, t) BCH sketch is
a strict *prefix* of the (n, t') vector over the same GF(2^m) — syndrome
column j depends only on j, never on t.  So a group that overloads its
decode budget can be rescued by shipping ONLY the incremental columns
S_{2t+1}..S_{2t'-1} and decoding the concatenation at t', with zero re-sent
bits and zero store rebuilds — instead of the legacy degradation ladder's
from-scratch doubled-d̂ re-plan.

Covered here, bottom-up: the prefix property itself, incremental decode ==
fresh decode (hypothesis), the kernel-path incremental sketch, the
``core.pbs.reconcile`` oracle's ladder, the wire pair / in-process server /
multi-peer hub / tree front end all byte-identical to that oracle, the
endpoint's strict MSG_PARITY state machine, and the satellite regression
that an escalation (the legacy fallback) never ledgers a settled unit's
bits twice.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.core.bch import (
    bch_code,
    decode_extended,
    decode_sketch,
    sketch_from_positions,
    sketch_increment,
)
from repro.core.gf2m import get_field
from repro.core.pbs import (
    MAX_PARITY_EXTENSIONS,
    PBSConfig,
    parity_extension_t,
    reconcile,
    true_diff,
)
from repro.core.simdata import make_pair
from repro.kernels.ops import sketch_groups, sketch_groups_range
from repro.net import (
    AliceEndpoint,
    BobEndpoint,
    HubEndpoint,
    InMemoryDuplex,
    run_hub,
    run_pair,
)
from repro.recon.server import ReconcileServer
from repro.wire.frames import WireError


# ---------------------------------------------------------------------------
# the prefix property
# ---------------------------------------------------------------------------


@given(
    m=st.integers(min_value=4, max_value=9),
    t0=st.integers(min_value=0, max_value=12),
    dt=st.integers(min_value=0, max_value=12),
)
@settings(max_examples=30, deadline=None)
def test_syndrome_matrix_range_is_column_slice(m, t0, dt):
    """The (n, t) syndrome matrix is a strict prefix of the (n, t') one:
    the range helper returns exactly the shared matrix's column slice, so
    concatenating a sketch with its increment IS the wider sketch."""
    gf = get_field(m)
    t1 = t0 + dt
    full = gf.syndrome_matrix(t1)
    if t0:
        np.testing.assert_array_equal(
            full[:, : t0 * m], gf.syndrome_matrix(t0)
        )
    np.testing.assert_array_equal(
        full[:, t0 * m :], gf.syndrome_matrix_range(t0, t1)
    )


def _check_incremental_decode(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.choice([15, 31, 63, 127, 255]))
    cap = (n - 1) // 2
    t = int(rng.integers(1, cap))
    t1 = int(rng.integers(t + 1, cap + 1))
    d = int(rng.integers(0, min(t1 + 3, n) + 1))
    pos = rng.choice(n, size=d, replace=False).astype(np.int64)
    code1 = bch_code(n, t1)
    prefix = sketch_from_positions(bch_code(n, t), pos)
    inc = sketch_increment(code1, pos, t)
    ok_i, pos_i = decode_extended(n, prefix, inc)
    ok_f, pos_f = decode_sketch(code1, sketch_from_positions(code1, pos))
    assert ok_i == ok_f
    np.testing.assert_array_equal(np.sort(pos_i), np.sort(pos_f))
    if d <= t1:
        assert ok_i and set(pos_i.tolist()) == set(pos.tolist())


@given(seed=st.integers(min_value=0, max_value=2**32 - 1))
@settings(max_examples=25, deadline=None)
def test_incremental_decode_matches_fresh_decode(seed):
    """decode(prefix ++ increment) at t' is byte-identical to decoding a
    fresh (n, t') sketch of the same positions — across random
    (n, t -> t') pairs and random difference sets (including d > t', where
    both must fail identically)."""
    _check_incremental_decode(seed)


@pytest.mark.parametrize("seed", range(12))
def test_incremental_decode_matches_fresh_decode_seeded(seed):
    """Deterministic mirror of the hypothesis property (always runs, even
    without the optional hypothesis dependency)."""
    _check_incremental_decode(seed)
    # and the matrix prefix property at a few fixed shapes
    for m, t0, t1 in ((4, 2, 5), (7, 0, 9), (8, 6, 6 + seed % 5)):
        gf = get_field(m)
        np.testing.assert_array_equal(
            gf.syndrome_matrix(t1)[:, t0 * m :],
            gf.syndrome_matrix_range(t0, t1),
        )


def test_kernel_incremental_sketch_concat_matches_full():
    """kernels.ops.sketch_groups_range: prefix sketch ++ incremental
    columns == the full sketch at the wider t, element for element."""
    rng = np.random.default_rng(3)
    n, t0, t1 = 127, 4, 11
    bitmaps = (rng.random((6, n)) < 0.3).astype(np.int32)
    lo = np.asarray(sketch_groups(jnp.asarray(bitmaps), bch_code(n, t0)))
    inc = np.asarray(
        sketch_groups_range(jnp.asarray(bitmaps), bch_code(n, t1), t0)
    )
    full = np.asarray(sketch_groups(jnp.asarray(bitmaps), bch_code(n, t1)))
    np.testing.assert_array_equal(np.concatenate([lo, inc], axis=1), full)


def test_parity_extension_ladder_is_capped_by_code():
    """The deterministic t-ladder doubles per level and clamps at the
    (n - 1) // 2 BCH decoding cap — both wire sides derive it with zero
    negotiation."""
    n = 127
    assert parity_extension_t(5, 0, n) == 5
    assert parity_extension_t(5, 1, n) == 10
    assert parity_extension_t(5, 2, n) == 20
    assert parity_extension_t(5, 4, n) == 63       # clamped at (n-1)//2
    assert parity_extension_t(40, 1, n) == 63      # immediate clamp
    assert MAX_PARITY_EXTENSIONS >= 2


# ---------------------------------------------------------------------------
# the oracle's ladder + every serving path byte-identical to it
# ---------------------------------------------------------------------------


def _wrongd_inputs():
    """A 10x-underestimated d̂: every group overloads round 1; only the
    rateless ladder (or the legacy escalation fallback) can finish it
    without splitting progress away."""
    a, b = make_pair(3000, 100, np.random.default_rng(10))
    return a, b, PBSConfig(seed=3, rateless=True), 10


def test_oracle_rateless_recovers_wrong_dhat():
    a, b, cfg, dk = _wrongd_inputs()
    res = reconcile(a, b, cfg, d_known=dk)
    assert res.success and res.diff == true_diff(a, b)
    # the honest plan for comparison: rateless recovery must stay within
    # the CI gate's envelope of the honestly-planned ledger
    honest = reconcile(a, b, cfg, d_known=100)
    assert res.bytes_sent <= 1.6 * honest.bytes_sent


def test_pair_rateless_wrongd_recovers_without_replan():
    """Wire acceptance: under a 10x-wrong d̂ the pair reconciles through
    MSG_PARITY extensions alone — zero degraded sessions, ledger
    byte-identical to the oracle."""
    a, b, cfg, dk = _wrongd_inputs()
    oracle = reconcile(a, b, cfg, d_known=dk)
    ta, tb = InMemoryDuplex.pair()
    alice, bob = AliceEndpoint(ta), BobEndpoint(tb)
    alice.submit(a, cfg=cfg, d_known=dk)
    bob.submit(b, cfg=cfg, d_known=dk)
    res = run_pair(alice, bob)[0]
    assert res.success and res.diff == true_diff(a, b)
    assert res.bytes_per_round == oracle.bytes_per_round
    assert res.bytes_sent == oracle.bytes_sent
    assert res.decode_failures == oracle.decode_failures
    assert alice.parity_extensions == bob.parity_extensions > 0
    assert alice.sessions_degraded == bob.sessions_degraded == 0
    assert bob.verified == [True]


def test_pair_rateless_honest_path_stays_byte_identical():
    """``rateless=True`` must not perturb the honest path: same frames,
    same ledger as the oracle (which shares the ladder), and extensions
    fire only when a group actually overloads."""
    a, b = make_pair(3000, 100, np.random.default_rng(10))
    cfg = PBSConfig(seed=3, rateless=True)
    oracle = reconcile(a, b, cfg, d_known=100)
    ta, tb = InMemoryDuplex.pair()
    alice, bob = AliceEndpoint(ta), BobEndpoint(tb)
    alice.submit(a, cfg=cfg, d_known=100)
    bob.submit(b, cfg=cfg, d_known=100)
    res = run_pair(alice, bob)[0]
    assert res.success and res.diff == true_diff(a, b)
    assert res.bytes_per_round == oracle.bytes_per_round
    assert res.bytes_sent == oracle.bytes_sent
    assert alice.parity_extensions == bob.parity_extensions
    assert alice.sessions_degraded == bob.sessions_degraded == 0


def test_server_rateless_wrongd_no_replan_no_rebuild():
    """In-process server acceptance: the rateless path keeps the settled
    stores resident — store builds stay at the initial upload count, no
    session ever takes the degradation ladder, and the ledger matches the
    oracle exactly."""
    a, b, cfg, dk = _wrongd_inputs()
    oracle = reconcile(a, b, cfg, d_known=dk)
    srv = ReconcileServer(degrade=True)
    srv.submit(a, b, cfg=cfg, d_known=dk)
    res = srv.run()[0]
    assert res.success and res.diff == true_diff(a, b)
    assert res.bytes_per_round == oracle.bytes_per_round
    assert res.bytes_sent == oracle.bytes_sent
    assert srv.stats["parity_extensions"] > 0
    assert srv.stats["sessions_degraded"] == 0
    # one initial upload per side, nothing rebuilt by the recovery
    assert srv.stats["store_builds"] == 1


def test_hub_rateless_peers_match_oracle():
    """Multi-peer hub: wrong-d̂ rateless peers recover over the shared
    cohort ladder (one incremental dispatch per cohort per level, fused
    across peers) while an honest rateless peer rides along untouched."""
    hub = HubEndpoint(recv_deadline=30.0)
    alices, cases = {}, {}
    specs = [
        (make_pair(3000, 100, np.random.default_rng(10)),
         PBSConfig(seed=3, rateless=True), 10),
        (make_pair(2000, 50, np.random.default_rng(12)),
         PBSConfig(seed=5, rateless=True), 50),
    ]
    for (a, b), cfg, dk in specs:
        ta, tb = InMemoryDuplex.pair()
        ch = hub.add_peer(tb)
        hub.submit(ch, b, cfg=cfg, d_known=dk)
        ep = AliceEndpoint(ta, channel=ch)
        ep.submit(a, cfg=cfg, d_known=dk)
        alices[ch] = ep
        cases[ch] = (a, b, cfg, dk)
    outcomes, results, errors = run_hub(hub, alices)
    assert not errors, errors
    for ch, (a, b, cfg, dk) in cases.items():
        exp = reconcile(a, b, cfg, d_known=dk)
        got = results[ch][0]
        assert got.diff == exp.diff == true_diff(a, b), ch
        assert got.bytes_per_round == exp.bytes_per_round, ch
        assert got.bytes_sent == exp.bytes_sent, ch
        assert outcomes[ch].ok and outcomes[ch].verified == [True], ch
        assert outcomes[ch].error_kind is None, ch      # never "degraded"
    assert hub.stats["parity_extensions"] > 0
    assert hub.stats["sessions_degraded"] == 0
    assert alices[1].parity_extensions > 0
    assert alices[2].parity_extensions == 0             # honest peer


def test_tree_rateless_leaf_recovery():
    """Tree front end: a leaf whose level-ℓ estimate undershot recovers
    ratelessly inside its round instead of escalating — and never costs
    more than the escalation path it replaces."""
    from repro.tree.partition import TreeConfig, tree_reconcile

    a, b = make_pair(6000, 300, np.random.default_rng(42))
    want = true_diff(a, b)
    legacy = tree_reconcile(a, b, PBSConfig(seed=9), TreeConfig())
    res = tree_reconcile(
        a, b, PBSConfig(seed=9), TreeConfig(), rateless=True
    )
    assert res.success and res.diff == want == legacy.diff
    assert res.total_bytes <= legacy.total_bytes


# ---------------------------------------------------------------------------
# the endpoint's strict MSG_PARITY state machine
# ---------------------------------------------------------------------------


def test_bob_rejects_out_of_band_parity_frames():
    """A MSG_PARITY frame the serving side does not expect fails the pair's
    serve loop with a clean WireError: before any round, in a round with
    no extension pending, and one level beyond MAX_PARITY_EXTENSIONS."""
    from repro.net import FrameStream
    from repro.wire import frames as wf

    refused = f"got 0x{wf.MSG_PARITY:02x}"
    a, b = make_pair(2000, 20, np.random.default_rng(12))

    # no round in flight at all: a parity frame where round 1's sketches
    # belong
    ta, tb = InMemoryDuplex.pair()
    bob = BobEndpoint(tb)
    bob.submit(b, cfg=PBSConfig(seed=2), d_known=20)
    FrameStream(ta).send(wf.encode_parity(1, 1, []))
    with pytest.raises(WireError, match=refused):
        bob.serve()

    # round in flight but no extension pending: a parity frame where the
    # outcome belongs, from a session that is not rateless
    class _ParityAfterReply(AliceEndpoint):
        def _rateless_ladder(self, rnd, plans, per, live, ent_of):
            self._stream.send(wf.encode_parity(rnd, 1, []))
            self._expect(wf.MSG_ROUND_REPLY)    # Bob dies first
            raise AssertionError("unreachable")

    ta, tb = InMemoryDuplex.pair()
    alice, bob = _ParityAfterReply(ta), BobEndpoint(tb)
    alice.submit(a, cfg=PBSConfig(seed=2), d_known=20)
    bob.submit(b, cfg=PBSConfig(seed=2), d_known=20)
    with pytest.raises(WireError, match=refused):
        run_pair(alice, bob)

    # ladder exhausted: a unit still failing after all MAX levels (t = 2
    # -> 32, under the (255 - 1) // 2 code cap), then one frame past them
    class _PastTheCap(AliceEndpoint):
        def _rateless_ladder(self, rnd, plans, per, live, ent_of):
            before = self.parity_extensions
            out = super()._rateless_ladder(rnd, plans, per, live, ent_of)
            if all(ok for sid in live for ok in ent_of[sid][0]):
                return out
            assert self.parity_extensions - before == MAX_PARITY_EXTENSIONS
            self._stream.send(
                wf.encode_parity(rnd, MAX_PARITY_EXTENSIONS + 1, [])
            )
            self._expect(wf.MSG_ROUND_REPLY)    # Bob dies first
            raise AssertionError("unreachable")

    cfg = PBSConfig(seed=6, n_override=255, t_override=2, g_override=1,
                    rateless=True)
    a, b = make_pair(2000, 400, np.random.default_rng(17))
    ta, tb = InMemoryDuplex.pair()
    alice, bob = _PastTheCap(ta), BobEndpoint(tb)
    alice.submit(a, cfg=cfg, d_known=400)
    bob.submit(b, cfg=cfg, d_known=400)
    with pytest.raises(WireError, match=refused):
        run_pair(alice, bob)
    assert alice.parity_extensions == bob.parity_extensions > 0


def test_bob_rejects_stale_round_parity():
    """A MSG_PARITY frame stamped with a stale round number fails the
    serve loop with a clean WireError instead of corrupting the ladder."""
    from repro.wire import frames as wf

    class _StaleParityAlice(AliceEndpoint):
        def _rateless_ladder(self, rnd, plans, per, live, ent_of):
            # derive a legitimate level-1 extension, then mis-stamp it
            from repro.net.endpoint import encode_round_rows_ext

            fail = {}
            for sid in live:
                row = per[sid]
                bad = [
                    s for s in range(len(row.active))
                    if not ent_of[sid][0][s]
                ]
                if bad:
                    fail[sid] = bad
            assert fail, "scenario must overload at least one group"
            part_plans = [
                plan for plan in plans
                if any(sess.sid in fail for sess, *_ in plan.members)
            ]
            inc_of = encode_round_rows_ext(
                part_plans, self.side, 1, self._interpret
            )
            parts = [sid for sid in live if sid in fail and sid in inc_of]
            blocks = [
                (inc_of[sid][0][fail[sid]], per[sid].plan.store.m)
                for sid in parts
            ]
            self._stream.send(wf.encode_parity(rnd + 7, 1, blocks))
            self._expect(wf.MSG_ROUND_REPLY)    # Bob dies first
            raise AssertionError("unreachable")

    a, b, cfg, dk = _wrongd_inputs()
    ta, tb = InMemoryDuplex.pair()
    alice, bob = _StaleParityAlice(ta), BobEndpoint(tb)
    alice.submit(a, cfg=cfg, d_known=dk)
    bob.submit(b, cfg=cfg, d_known=dk)
    with pytest.raises(WireError, match="parity frame for round"):
        run_pair(alice, bob)


# ---------------------------------------------------------------------------
# satellite regression: escalation (the legacy fallback) carries progress
# ---------------------------------------------------------------------------


def _escalation_inputs():
    """Tight round budget + underestimated d̂, rateless OFF: only the
    legacy degradation ladder can finish, and it must do so without
    re-transmitting settled units."""
    a, b = make_pair(4000, 1000, np.random.default_rng(7))
    return a, b, PBSConfig(seed=5, max_rounds=2), 250


def test_escalation_carries_settled_progress(monkeypatch):
    """No settled unit's bits are ledgered twice across an escalation: the
    carrying ladder's total is strictly below a no-carry ladder that
    forgets the recovered diff (forcing settled elements back onto the
    wire), and the carried ledger still sums consistently."""
    import repro.recon.session as rs

    a, b, cfg, dk = _escalation_inputs()
    want = true_diff(a, b)

    srv = ReconcileServer(degrade=True)
    srv.submit(a, b, cfg=cfg, d_known=dk)
    res = srv.run()[0]
    assert res.success and res.diff == want
    assert srv.stats["sessions_degraded"] >= 1
    assert sum(res.bytes_per_round) == res.bytes_sent

    # ablation: drop ONLY the recovered-diff carry (counters still carry
    # so the ledgers stay comparable) — settled elements re-enter the
    # effective sets and their bits are paid for again
    import repro.recon.server as rsrv

    real = rs.escalate_session

    def no_carry(batch, sess, *, rnd0):
        out = real(batch, sess, rnd0=rnd0)
        out.state.diff = set()
        return out

    monkeypatch.setattr(rs, "escalate_session", no_carry)
    monkeypatch.setattr(rsrv, "escalate_session", no_carry)
    srv0 = ReconcileServer(degrade=True)
    srv0.submit(a, b, cfg=cfg, d_known=dk)
    res0 = srv0.run()[0]
    monkeypatch.undo()
    assert res0.success and res0.diff == want
    assert res.bytes_sent < res0.bytes_sent


def test_escalation_cap_is_shared_single_source():
    """Satellite: the ladder caps are hoisted to core.pbs and threaded
    everywhere — no duplicated literals to drift apart."""
    import inspect

    from repro.core.pbs import MAX_ESCALATIONS
    from repro.recon import session as rs
    from repro.recon import server as srv_mod

    assert (
        inspect.signature(rs.degrade_exhausted)
        .parameters["max_escalations"].default is MAX_ESCALATIONS
    )
    assert (
        inspect.signature(srv_mod.ReconcileServer._escalate_exhausted)
        .parameters["max_escalations"].default is MAX_ESCALATIONS
    )


# ---------------------------------------------------------------------------
# resume safety: a crash mid-ladder never double-applies an extension
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("crash_after", [2, 3])
def test_crash_resume_mid_ladder_stays_byte_identical(crash_after):
    """The MSG_PARITY exchange is pre-barrier state: a peer crashing while
    the ladder is in flight resumes at equal barriers (the whole round —
    sketches, extensions, outcome — re-runs from scratch) or replays the
    one committed outcome frame, and either way the final Formula-(1)
    ledger is byte-identical to the rateless oracle."""
    import threading

    from repro.net import ChaosTransport, FaultPlan, TransportError

    a, b, cfg, dk = _wrongd_inputs()
    oracle = reconcile(a, b, cfg, d_known=dk)

    t_a_raw, t_h = InMemoryDuplex.pair()
    t_a = ChaosTransport(t_a_raw, FaultPlan(crash_after_sends=crash_after))
    hub = HubEndpoint(resume_window=30.0, recv_deadline=10.0)
    ch = hub.add_peer(t_h, label="ladder-crasher")
    hub.submit(ch, b, cfg=cfg, d_known=dk)
    ep = AliceEndpoint(t_a, channel=ch)
    ep.submit(a, cfg=cfg, d_known=dk)

    pending: dict = {}

    def on_barrier(rnd):
        if "t" in pending and hub._peers[ch].suspended:
            hub.resume_peer(ch, pending.pop("t"))

    hub.on_barrier = on_barrier
    state: dict = {}

    def drive():
        try:
            state["res"] = ep.run()
            return
        except TransportError as e:
            state["crash"] = e
        na, nh = InMemoryDuplex.pair()
        pending["t"] = nh
        ep.resume(na)
        state["res"] = ep.resume_run()

    th = threading.Thread(target=drive, daemon=True)
    th.start()
    outcomes = hub.serve()
    th.join(timeout=60)
    assert not th.is_alive(), "peer thread leaked"
    assert "crash" in state, "scripted crash never fired"

    res = state["res"][0]
    assert outcomes[ch].ok and outcomes[ch].verified == [True]
    assert outcomes[ch].error_kind == "resumed"
    assert res.success and res.diff == oracle.diff == true_diff(a, b)
    assert res.bytes_per_round == oracle.bytes_per_round
    assert res.bytes_sent == oracle.bytes_sent
    assert hub.stats["sessions_degraded"] == 0
    assert hub.stats["parity_extensions"] > 0
