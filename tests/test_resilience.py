"""Peer resurrection and chaos hardening (DESIGN.md §13).

The ISSUE 7 acceptance scenarios:

* the ``MSG_RESUME`` machinery — codec strictness, the rolling transcript
  digest, and crash→reconnect→resume against a live hub in both handshake
  cases (equal barriers; hub one outcome frame behind, replayed) — with the
  resumed peer's Formula-(1) ledger byte-identical to ``core.pbs.reconcile``
  and every replayed/handshake byte ledgered as transport overhead;
* the typed failure taxonomy (``PeerOutcome.error_kind``) and the adaptive
  ARQ retry state (``retransmits``/``rto_ms``) surfaced in wire stats;
* graceful degradation: a decode-budget-exhausted session escalates
  (doubled d̂ re-plan, ``sessions_degraded``) instead of failing, and the
  server / pair / hub paths agree byte-for-byte;
* the seeded chaos soak: a 6-peer continuous-sync hub under scripted
  loss bursts, duplication, reordering, a partition window and a scripted
  corruption, where 2 peers crash-restart mid-epoch (one clean disconnect,
  one silent crash caught by the barrier deadline) and resume via
  ``MSG_RESUME`` — every peer byte-identical to the oracle, zero store
  rebuilds, zero full re-syncs, replay bytes bounded by one round barrier
  per resumption.

The ≥20-epoch soak is marked ``slow`` (CI's non-blocking chaos-soak job);
the 3-epoch variant — same machinery, same assertions — runs in the
blocking fast tier.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.pbs import PBSConfig, reconcile, true_diff
from repro.core.simdata import make_pair
from repro.net import (
    AliceEndpoint,
    BobEndpoint,
    ChaosTransport,
    FaultPlan,
    HubEndpoint,
    InMemoryDuplex,
    PeerDeadline,
    ReliableTransport,
    Transport,
    TransportError,
    TransportTimeout,
    classify_error,
    run_hub,
    run_pair,
)
from repro.net.endpoint import stream_wire_stats
from repro.net.hub import _drive_hub
from repro.net.transport import FrameStream
from repro.recon.server import ReconcileServer
from repro.wire import frames as wf
from repro.wire.frames import WireError

# the replayed outcome frame of one round barrier (1 session, g <= 8 units)
# is far under this; the soak's replay ledger must stay within it per resume
_BARRIER_FRAME_BOUND = 64


# ---------------------------------------------------------------------------
# MSG_RESUME codec + transcript digest
# ---------------------------------------------------------------------------


def test_resume_codec_roundtrip_and_overhead():
    from repro.wire.varint import decode_uvarint

    f = wf.encode_resume(3, 7, 12, 0xDEADBEEFCAFEF00D, 0x0123456789ABCDEF)
    assert len(f) == wf.resume_overhead_bytes(3, 7, 12)
    # strip the frame header: uvarint(1+len) || type || payload
    _, off = decode_uvarint(f)
    assert f[off] == wf.MSG_RESUME
    ch, epoch, rnd, dig, dig_prev = wf.decode_resume(f[off + 1 :])
    assert (ch, epoch, rnd) == (3, 7, 12)
    assert dig == 0xDEADBEEFCAFEF00D and dig_prev == 0x0123456789ABCDEF


def test_resume_codec_strictness():
    with pytest.raises(WireError):
        wf.encode_resume(0, 0, 0, 0, 0)          # channel 0 is reserved
    with pytest.raises(WireError):
        wf.encode_resume(1, 0, -1, 0, 0)         # negative barrier
    from repro.wire.varint import decode_uvarint, encode_uvarint

    good = wf.encode_resume(2, 1, 3, 5, 6)
    _, off = decode_uvarint(good)
    payload = good[off + 1 :]
    with pytest.raises(WireError):
        wf.decode_resume(payload[:-1])           # truncated digest
    bad_ch = encode_uvarint(0) + payload[1:]
    with pytest.raises(WireError):
        wf.decode_resume(bad_ch)                 # channel 0 on decode too


def test_transcript_digest_determinism_and_sensitivity():
    d0 = wf.transcript_digest0(0)
    assert d0 == wf.transcript_digest0(0)
    assert d0 != wf.transcript_digest0(1)        # epoch-seeded
    frame = wf.frame(wf.MSG_ROUND_OUTCOME, b"\x01\x02\x03")
    a = wf.fold_transcript(d0, 1, frame)
    assert a == wf.fold_transcript(d0, 1, frame)
    assert a != d0
    assert a != wf.fold_transcript(d0, 2, frame)             # round-sensitive
    assert a != wf.fold_transcript(d0, 1, frame[:-1] + b"\x04")  # byte-sensitive
    # folding is ordered: (r1, f1) then (r2, f2) != (r2, f2) then (r1, f1)
    f2 = wf.frame(wf.MSG_ROUND_OUTCOME, b"\x05")
    assert (
        wf.fold_transcript(wf.fold_transcript(d0, 1, frame), 2, f2)
        != wf.fold_transcript(wf.fold_transcript(d0, 2, f2), 1, frame)
    )


# ---------------------------------------------------------------------------
# error taxonomy
# ---------------------------------------------------------------------------


def test_classify_error_taxonomy():
    assert classify_error(None) is None
    assert classify_error(PeerDeadline("x")) == "deadline"
    assert classify_error(TransportTimeout("x")) == "deadline"
    assert classify_error(WireError("x")) == "wire"
    assert classify_error(TransportError("x")) == "transport"
    assert classify_error(ValueError("x")) == "error"
    # eviction re-wraps the root failure in a TransportError; the root wins
    wrapped = TransportError("peer: bad frame")
    wrapped.__cause__ = WireError("bad frame")
    assert classify_error(wrapped) == "wire"
    expired = PeerDeadline("resume window expired")
    expired.__cause__ = PeerDeadline("missed barrier")
    assert classify_error(expired) == "deadline"
    # a transport wrapper over an unclassified cause stays transport
    plain = TransportError("closed")
    plain.__cause__ = ValueError("boom")
    assert classify_error(plain) == "transport"
    # the estimator regime guard (DESIGN.md §15) is its own class, both
    # bare and through the eviction wrapper
    from repro.core.tow import EstimateOutOfRange

    oor = EstimateOutOfRange(900, 1000, 0.5)
    assert classify_error(oor) == "estimate"
    wrapped_oor = TransportError("peer: estimate out of range")
    wrapped_oor.__cause__ = oor
    assert classify_error(wrapped_oor) == "estimate"


# ---------------------------------------------------------------------------
# adaptive ARQ retry (satellite: backoff + jitter + cap, stats surfaced)
# ---------------------------------------------------------------------------


def test_rto_backs_off_caps_and_resets_on_delivery():
    from repro.wire.varint import decode_uvarint, encode_uvarint

    raw, side = InMemoryDuplex.pair()
    rt = ReliableTransport(side, timeout=0.01, max_retries=4,
                           rto_max=0.08, backoff=2.0, jitter=0.0)
    assert rt.rto_ms == pytest.approx(10.0)
    with pytest.raises(TransportError, match="no ACK"):
        rt.send(b"void")
    # 0.01 -> 0.02 -> 0.04 -> 0.08 (capped); attempts counted as retransmits
    assert rt.rto_ms == pytest.approx(80.0)
    assert rt.retransmits == 3

    # drain the failed send's queued retransmits, then a delivered ACK
    # resets the timer to the base timeout
    while True:
        try:
            raw.recv(timeout=0.01)
        except TransportTimeout:
            break

    def _ack():
        dgram = raw.recv(timeout=2.0)
        seq, _ = decode_uvarint(dgram, 1)
        raw.send(bytes((0x01,)) + encode_uvarint(seq))

    th = threading.Thread(target=_ack, daemon=True)
    th.start()
    rt.send(b"delivered")
    th.join(2.0)
    assert rt.rto_ms == pytest.approx(10.0)

    # both counters surface through the endpoint wire-stats contract
    tally = {"estimator": 0, "protocol": 0, "verify": 0, "epoch": 0,
             "resume": 0}
    st = stream_wire_stats(FrameStream(rt), tally)
    assert st["retransmits"] == rt.retransmits >= 3
    assert st["rto_ms"] == pytest.approx(10.0)
    assert st["resume_frame_bytes"] == 0


def test_rto_jitter_is_seeded_and_bounded():
    rts = [
        ReliableTransport(InMemoryDuplex.pair()[1], timeout=0.1,
                          jitter=0.25, seed=9)
        for _ in range(2)
    ]
    waits = [[rt._attempt_wait() for _ in range(32)] for rt in rts]
    assert waits[0] == waits[1]                  # same seed, same schedule
    assert all(0.075 <= w <= 0.125 for w in waits[0])
    assert len(set(waits[0])) > 1                # actually randomized


# ---------------------------------------------------------------------------
# FaultPlan / ChaosTransport
# ---------------------------------------------------------------------------


class _Sink(Transport):
    def __init__(self):
        super().__init__()
        self.delivered: list[bytes] = []
        self.closed = False

    def send(self, data: bytes) -> None:
        self.delivered.append(bytes(data))

    def recv(self, timeout: float | None = None) -> bytes:
        raise TransportTimeout("sink")

    def close(self) -> None:
        self.closed = True


def _run_plan(plan: FaultPlan, n_ops: int = 200):
    sink = _Sink()
    ct = ChaosTransport(sink, plan)
    for i in range(n_ops):
        try:
            ct.send(bytes((0x00, i % 256)))
        except TransportError:
            break
    return ct, sink


def test_chaos_same_seed_same_faults():
    plan = FaultPlan(seed=5, loss=0.15, dup=0.1, reorder=0.1, corrupt=0.05)
    a, sink_a = _run_plan(plan)
    b, sink_b = _run_plan(plan)
    assert sink_a.delivered == sink_b.delivered
    assert (a.dropped, a.duplicated, a.reordered, a.corrupted) == (
        b.dropped, b.duplicated, b.reordered, b.corrupted
    )
    assert a.dropped > 0 and a.duplicated > 0 and a.corrupted > 0
    # a different seed yields a different fault pattern
    _, sink_c = _run_plan(
        FaultPlan(seed=6, loss=0.15, dup=0.1, reorder=0.1, corrupt=0.05)
    )
    assert sink_c.delivered != sink_a.delivered


def test_chaos_scripted_faults_are_exact():
    # partition blackholes exactly ops [2, 5); burst drops the first 2 of
    # every 10; corrupt_at garbles exactly op 7's first byte
    plan = FaultPlan(partitions=((2, 5),), burst_every=10, burst_len=2,
                     corrupt_at=(7,))
    ct, sink = _run_plan(plan, n_ops=12)
    # dropped: ops 0,1 (burst), 2,3,4 (partition), 10,11 (burst) = 7
    assert ct.dropped == 7
    delivered_ops = [5, 6, 7, 8, 9]
    assert len(sink.delivered) == len(delivered_ops)
    for dgram, op in zip(sink.delivered, delivered_ops):
        want = bytes((0x00 ^ (0x80 if op == 7 else 0x00), op))
        assert dgram == want
    assert ct.corrupted == 1


def test_chaos_scripted_crash_clean_and_silent():
    clean, sink = _run_plan(FaultPlan(crash_after_sends=3), n_ops=10)
    assert clean.crashed and clean.sends == 4 and sink.closed
    with pytest.raises(TransportError):
        clean.recv(timeout=0.01)
    silent, sink2 = _run_plan(
        FaultPlan(crash_after_sends=3, crash_silent=True), n_ops=10
    )
    # silent crash: the crashed side fails fast, but the channel is NOT
    # closed — the remote observes pure silence (the deadline path)
    assert silent.crashed and not sink2.closed
    with pytest.raises(TransportError):
        silent.send(b"x")


def test_chaos_reorder_swaps_adjacent_pairs():
    plan = FaultPlan(seed=1, reorder=1.0)     # hold every datagram
    sink = _Sink()
    ct = ChaosTransport(sink, plan)
    for i in range(4):
        ct.send(bytes((0x00, i)))
    # every odd send releases the held predecessor after itself
    assert [d[1] for d in sink.delivered] == [1, 0, 3, 2]
    assert ct.reordered == 2


# ---------------------------------------------------------------------------
# crash -> reconnect -> resume against a live hub (both handshake cases)
# ---------------------------------------------------------------------------


def _crash_resume(crash_after: int):
    """One peer crashing after ``crash_after`` sends, reconnecting and
    resuming; returns (hub, alice, outcome, result, oracle, channel)."""
    rng = np.random.default_rng(7)
    univ = rng.choice(1 << 20, size=3000, replace=False).astype(np.uint32)
    a, b = univ[:2600], univ[400:]
    cfg = PBSConfig(seed=3)
    d = len(np.setxor1d(a, b))

    t_a_raw, t_h = InMemoryDuplex.pair()
    t_a = ChaosTransport(t_a_raw, FaultPlan(crash_after_sends=crash_after))
    hub = HubEndpoint(resume_window=30.0, recv_deadline=10.0)
    ch = hub.add_peer(t_h, label="crasher")
    hub.submit(ch, b, cfg=cfg, d_known=d)
    ep = AliceEndpoint(t_a, channel=ch)
    ep.submit(a, cfg=cfg, d_known=d)

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
    oracle = reconcile(a, b, cfg, d_known=d)
    return hub, ep, outcomes[ch], state["res"][0], oracle, ch


@pytest.mark.parametrize(
    "crash_after,case",
    [
        (1, "replay: outcome frame died in flight, hub one barrier behind"),
        (2, "equal barriers: crash between completed rounds"),
    ],
)
def test_crash_resume_byte_identical(crash_after, case):
    hub, ep, outcome, res, oracle, ch = _crash_resume(crash_after)
    st = hub.stats

    assert outcome.ok and outcome.verified == [True], case
    assert outcome.error_kind == "resumed", case
    assert ep.resumes == 1 and st["peers_resumed"] == 1
    assert st.get("peers_failed", 0) == 0

    # the resumed protocol's Formula-(1) ledger is byte-identical to the
    # fresh oracle: the crash cost lives only in the transport-overhead
    # resume tally, never in the protocol bits
    assert res.success and res.diff == oracle.diff
    assert res.rounds == oracle.rounds
    assert res.bytes_per_round == oracle.bytes_per_round, case
    assert res.bytes_sent == oracle.bytes_sent, case

    aw = ep.wire_stats
    hw = hub._peers[ch].wire_stats()
    # both sides ledger the same resume overhead (handshake + any replay)
    assert aw["resume_frame_bytes"] == hw["resume_frame_bytes"] > 0
    if crash_after == 1:
        # the hub missed exactly one outcome frame: it was replayed and
        # ledgered as resume overhead, bounded by one barrier frame — so
        # the hub's protocol tally is short exactly that frame (it only
        # ever received the replayed copy)
        assert 0 < st["resume_replay_bytes"] <= _BARRIER_FRAME_BOUND
        assert aw["protocol_frame_bytes"] == (
            hw["protocol_frame_bytes"] + st["resume_replay_bytes"]
        )
    else:
        assert st["resume_replay_bytes"] == 0
        assert aw["protocol_frame_bytes"] == hw["protocol_frame_bytes"]


def test_silent_crash_suspends_at_deadline_then_resumes():
    """A peer going dark (silent crash) is caught by the hub's barrier
    deadline, suspended as resumable, and resumes cleanly."""
    rng = np.random.default_rng(9)
    univ = rng.choice(1 << 20, size=2400, replace=False).astype(np.uint32)
    a, b = univ[:2100], univ[300:]
    cfg = PBSConfig(seed=4)
    d = len(np.setxor1d(a, b))

    # a warm pair at the same shapes compiles every round's executors
    # first, so the 1 s barrier deadline below times the silent crash alone
    t_a_raw, t_h = InMemoryDuplex.pair()
    warm_a, warm_b = AliceEndpoint(t_a_raw), BobEndpoint(t_h)
    warm_a.submit(a, cfg=cfg, d_known=d)
    warm_b.submit(b, cfg=cfg, d_known=d)
    assert run_pair(warm_a, warm_b)[0].success

    t_a_raw, t_h = InMemoryDuplex.pair()
    t_a = ChaosTransport(
        t_a_raw, FaultPlan(crash_after_sends=2, crash_silent=True)
    )
    hub = HubEndpoint(resume_window=30.0, recv_deadline=1.0)
    ch = hub.add_peer(t_h, label="dark")
    hub.submit(ch, b, cfg=cfg, d_known=d)
    ep = AliceEndpoint(t_a, channel=ch)
    ep.submit(a, cfg=cfg, d_known=d)

    pending: dict = {}
    kinds: list = []

    def on_barrier(rnd):
        if "t" in pending and hub._peers[ch].suspended:
            kinds.append(classify_error(hub._peers[ch].suspend_err))
            hub.resume_peer(ch, pending.pop("t"))

    hub.on_barrier = on_barrier

    def drive():
        try:
            ep.run()
            return
        except TransportError:
            pass
        na, nh = InMemoryDuplex.pair()
        pending["t"] = nh
        ep.resume(na)
        ep.resume_run()

    th = threading.Thread(target=drive, daemon=True)
    th.start()
    outcomes = hub.serve()
    th.join(timeout=60)
    assert not th.is_alive()
    assert kinds == ["deadline"]         # caught by PeerDeadline, not close
    assert outcomes[ch].ok and outcomes[ch].error_kind == "resumed"
    assert hub.stats["peers_resumed"] == 1


class _LostAck(Transport):
    """The hub's side of one peer: once ``armed``, the first send of an
    inner frame of type ``kind`` fails after the frame was delivered (only
    its transport ack was lost) or before (``delivered=False``)."""

    def __init__(self, inner: Transport, kind: int, delivered: bool):
        super().__init__()
        self.inner, self.kind, self.delivered = inner, kind, delivered
        self.armed = self.fired = False

    def send(self, data: bytes) -> None:
        _, payload, _ = wf.split_frame(bytearray(data), 0)
        _, inner_type, _ = wf.decode_mux(payload)
        if self.armed and not self.fired and inner_type == self.kind:
            self.fired = True
            if self.delivered:
                self.inner.send(data)
            self.inner.close()
            raise TransportError("lost ack" if self.delivered else "lost")
        self.inner.send(data)

    def recv(self, timeout: float | None = None) -> bytes:
        return self.inner.recv(timeout)

    def close(self) -> None:
        self.inner.close()


@pytest.mark.parametrize("delivered", [True, False], ids=["arrived", "lost"])
@pytest.mark.parametrize(
    "kind,dk",
    [(wf.MSG_EPOCH, None), (wf.MSG_ROUND_REPLY, 40), (wf.MSG_VERIFY_ACK, 40)],
    ids=["epoch_reply", "round_reply", "verify_ack"],
)
def test_send_failure_resumes_whether_or_not_it_arrived(kind, dk, delivered):
    """A hub send that raises may still have reached the peer. Whichever
    it was — an epoch reply, a round reply or a verify ack — the epoch
    completes byte-identical to the oracle: the peer resumes from where it
    stands, or (an ack it got) never needs to."""
    a, b = make_pair(1500, 40, np.random.default_rng(5))
    cfg = PBSConfig(seed=3, **_CFG)
    hub = HubEndpoint(continuous=True, resume_window=5.0, recv_deadline=10.0)
    ta, th = InMemoryDuplex.pair()
    lossy = _LostAck(th, kind, delivered)
    ch = hub.add_peer(lossy)
    hub.submit(ch, b, cfg=cfg, d_known=dk)
    ep = AliceEndpoint(ta, channel=ch, continuous=True)
    ep.submit(a, cfg=cfg, d_known=dk)
    pending: dict = {}

    def on_barrier(rnd):
        if "t" in pending and hub._peers[ch].suspended:
            hub.resume_peer(ch, pending.pop("t"))

    hub.on_barrier = on_barrier

    def resuming(fn):
        def call():
            try:
                return fn()
            except TransportError:
                pass
            na, pending["t"] = InMemoryDuplex.pair()
            ep.resume(na)
            return ep.resume_run()
        return call

    outcomes, _, errors = _drive_hub(hub, {ch: resuming(ep.run)}, 60.0)
    assert not errors and outcomes[ch].ok
    rng = np.random.default_rng(1)
    hub.advance_epoch({ch: {0: (_fresh_elems(rng, 20), b[:10])}})
    ep.advance_epoch({0: (_fresh_elems(rng, 5), ep.sessions[0].state.a[:5])})
    lossy.armed = True
    outcomes, results, errors = _drive_hub(
        hub, {ch: resuming(ep.run_epoch)}, 60.0
    )
    assert lossy.fired
    assert not errors, errors
    assert outcomes[ch].ok and outcomes[ch].verified == [True]
    a_e, b_e = ep.sessions[0].state.a, hub._peers[ch].sessions[0].state.b
    oracle = reconcile(a_e, b_e, cfg, d_known=dk)
    res = results[ch][0]
    assert res.diff == oracle.diff == true_diff(a_e, b_e)
    assert res.bytes_per_round == oracle.bytes_per_round
    # an ack the peer got needs no resume; anything else takes one
    assert ep.resumes == int(not (delivered and kind == wf.MSG_VERIFY_ACK))
    assert hub.stats.get("peers_failed", 0) == 0


def test_resume_rejected_on_diverged_transcript():
    """A reconnecting peer whose transcript digest diverged must be refused
    at the handshake (evicted as a wire failure), never re-attached."""
    rng = np.random.default_rng(13)
    univ = rng.choice(1 << 20, size=2400, replace=False).astype(np.uint32)
    a, b = univ[:2100], univ[300:]
    cfg = PBSConfig(seed=6)
    d = len(np.setxor1d(a, b))

    t_a_raw, t_h = InMemoryDuplex.pair()
    t_a = ChaosTransport(t_a_raw, FaultPlan(crash_after_sends=2))
    hub = HubEndpoint(resume_window=30.0, recv_deadline=5.0)
    ch = hub.add_peer(t_h, label="diverged")
    hub.submit(ch, b, cfg=cfg, d_known=d)
    ep = AliceEndpoint(t_a, channel=ch)
    ep.submit(a, cfg=cfg, d_known=d)

    pending: dict = {}
    hub_err: list = []

    def on_barrier(rnd):
        if "t" in pending and hub._peers[ch].suspended:
            try:
                hub.resume_peer(ch, pending.pop("t"))
            except WireError as e:
                hub_err.append(e)

    hub.on_barrier = on_barrier
    alice_err: list = []

    def drive():
        try:
            ep.run()
            return
        except TransportError:
            pass
        ep._digest ^= 0x1          # simulated divergence / stale snapshot
        na, nh = InMemoryDuplex.pair()
        pending["t"] = nh
        try:
            ep.resume(na)
            ep.resume_run()
        except (TransportError, WireError) as e:
            alice_err.append(e)

    th = threading.Thread(target=drive, daemon=True)
    th.start()
    outcomes = hub.serve()
    th.join(timeout=60)
    assert not th.is_alive()
    assert hub_err and "diverged" in str(hub_err[0])
    assert alice_err, "the refused peer must fail fast, not hang"
    assert not outcomes[ch].ok
    assert outcomes[ch].error_kind == "wire"
    assert hub.stats["peers_resumed"] == 0
    assert ch in hub.stale_channels


def test_suspension_expires_into_classified_eviction():
    """A suspended peer that never reconnects hardens into an eviction
    once the resume window lapses, keeping the root failure's class."""
    rng = np.random.default_rng(17)
    univ = rng.choice(1 << 20, size=2400, replace=False).astype(np.uint32)
    a, b = univ[:2100], univ[300:]
    cfg = PBSConfig(seed=8)
    d = len(np.setxor1d(a, b))

    t_a_raw, t_h = InMemoryDuplex.pair()
    t_a = ChaosTransport(t_a_raw, FaultPlan(crash_after_sends=2))
    hub = HubEndpoint(resume_window=0.3, recv_deadline=5.0)
    ch = hub.add_peer(t_h, label="gone")
    hub.submit(ch, b, cfg=cfg, d_known=d)
    ep = AliceEndpoint(t_a, channel=ch)
    ep.submit(a, cfg=cfg, d_known=d)

    def drive():
        with pytest.raises(TransportError):
            ep.run()

    th = threading.Thread(target=drive, daemon=True)
    th.start()
    outcomes = hub.serve()
    th.join(timeout=60)
    assert not th.is_alive()
    assert not outcomes[ch].ok
    assert "resume window" in str(outcomes[ch].error)
    assert outcomes[ch].error_kind == "transport"
    st = hub.stats
    assert st["peers_failed"] == 1
    assert st["peers_failed_by_kind"] == {"transport": 1}
    assert st["peers_resumed"] == 0


# ---------------------------------------------------------------------------
# graceful degradation: server / pair / hub agree
# ---------------------------------------------------------------------------


def _degradation_inputs():
    rng = np.random.default_rng(11)
    univ = rng.choice(1 << 20, size=4000, replace=False).astype(np.uint32)
    a, b = univ[:3500], univ[500:]
    # d = 1000 but the session claims d̂ = 250: the round budget exhausts
    # and only the escalation ladder (250 -> 500 -> 1000) can finish it
    return a, b, PBSConfig(seed=5, max_rounds=2), 250


def test_degradation_completes_exhausted_session_across_paths():
    a, b, cfg, dk = _degradation_inputs()
    want = true_diff(a, b)

    # the in-process server is the degradation oracle
    srv = ReconcileServer(degrade=True)
    srv.submit(a, b, cfg=cfg, d_known=dk)
    oracle = srv.run()[0]
    assert oracle.success and oracle.diff == want
    assert srv.stats["sessions_degraded"] >= 1

    # without degradation the same inputs fail (the scenario is real)
    srv0 = ReconcileServer()
    srv0.submit(a, b, cfg=cfg, d_known=dk)
    assert not srv0.run()[0].success

    # wire pair, degrade on both ends: byte-identical to the server path
    ta, tb = InMemoryDuplex.pair()
    alice, bob = AliceEndpoint(ta, degrade=True), BobEndpoint(tb, degrade=True)
    alice.submit(a, cfg=cfg, d_known=dk)
    bob.submit(b, cfg=cfg, d_known=dk)
    res = run_pair(alice, bob)[0]
    assert res.success and res.diff == want
    assert alice.sessions_degraded == bob.sessions_degraded >= 1
    assert res.bytes_per_round == oracle.bytes_per_round
    assert res.bytes_sent == oracle.bytes_sent

    # hub path: same ledger, outcome tagged "degraded"
    th_a, th_h = InMemoryDuplex.pair()
    hub = HubEndpoint(degrade=True, recv_deadline=20.0)
    ch = hub.add_peer(th_h)
    hub.submit(ch, b, cfg=cfg, d_known=dk)
    ep = AliceEndpoint(th_a, channel=ch, degrade=True)
    ep.submit(a, cfg=cfg, d_known=dk)
    outcomes, results, errors = run_hub(hub, {ch: ep})
    assert not errors, errors
    r = results[ch][0]
    assert r.success and r.diff == want
    assert r.bytes_per_round == oracle.bytes_per_round
    assert r.bytes_sent == oracle.bytes_sent
    assert hub.stats["sessions_degraded"] >= 1
    assert outcomes[ch].ok and outcomes[ch].error_kind == "degraded"


def test_degradation_ladder_is_capped():
    """Escalation stops at the cap: a hopeless d̂ still fails (bounded
    work), it just fails after the ladder instead of silently looping."""
    a, b, cfg, _ = _degradation_inputs()
    srv = ReconcileServer(degrade=True)
    srv.submit(a, b, cfg=cfg, d_known=8)   # 8 -> 16 -> 32 -> 64 << 1000
    res = srv.run()[0]
    assert not res.success
    assert srv.stats["sessions_degraded"] == 3      # the whole ladder, once


# ---------------------------------------------------------------------------
# the chaos soak
# ---------------------------------------------------------------------------

_CFG = dict(n_override=127, t_override=7, g_override=4)


def _fresh_elems(rng, k):
    return rng.integers(1, 1 << 32, size=k, dtype=np.uint64).astype(np.uint32)


def _chaos_soak(epochs, *, crash_epochs=(1,), corrupt_op=None, seed=0,
                deadline=4.0):
    """A 6-peer continuous hub under scripted chaos.

    Peer roles: 0 crash-restarts by clean disconnect and 1 by silent crash
    (both at the first round barrier of every epoch in ``crash_epochs``,
    resuming mid-epoch via MSG_RESUME); 2 runs its whole life behind a
    seeded lossy/duplicating/reordering ARQ channel with a partition
    window; 3 rides ARQ with one scripted corruption (detected, then
    healed by suspend→resume); 4 re-estimates d̂ every epoch; 5 is clean.
    """
    peers = 6
    d = 60
    rng = np.random.default_rng(seed)
    hub = HubEndpoint(recv_deadline=deadline, continuous=True,
                      resume_window=60.0)
    alices: dict[int, AliceEndpoint] = {}
    cfgs: dict[int, PBSConfig] = {}
    dks: dict[int, int | None] = {}
    conn: dict[int, dict] = {}     # per-channel live transport + chaos refs
    roles: dict[str, int] = {}

    plan2 = FaultPlan(seed=seed + 50, loss=0.08, burst_every=40, burst_len=2,
                      dup=0.06, reorder=0.06, partitions=((120, 126),))
    plan3 = (FaultPlan(seed=seed + 60, corrupt_at=(corrupt_op,))
             if corrupt_op is not None else FaultPlan(seed=seed + 60))

    for p in range(peers):
        a, b = make_pair(700, d, np.random.default_rng(seed + 101 * p))
        dk = None if p == 4 else d
        cfg = PBSConfig(seed=seed + p, **_CFG)
        if p in (2, 3):
            raw_a, raw_h = InMemoryDuplex.pair()
            chaos = ChaosTransport(raw_a, plan2 if p == 2 else plan3)
            ta = ReliableTransport(chaos, timeout=0.02, max_retries=400,
                                   seed=p)
            th = ReliableTransport(raw_h, timeout=0.02, max_retries=400,
                                   seed=100 + p)
        else:
            ta, th = InMemoryDuplex.pair()
            chaos = None
            if p == 1:
                chaos = ChaosTransport(ta, FaultPlan(crash_silent=True))
                ta = chaos
        ch = hub.add_peer(th, label=f"peer{p}")
        hub.submit(ch, b, cfg=cfg, d_known=dk)
        ep = AliceEndpoint(ta, channel=ch, continuous=True)
        ep.submit(a, cfg=cfg, d_known=dk)
        alices[ch] = ep
        cfgs[ch], dks[ch] = cfg, dk
        conn[ch] = {"ta": ta, "chaos": chaos}
        roles[f"p{p}"] = ch

    ch0, ch1 = roles["p0"], roles["p1"]
    ch2, ch3 = roles["p2"], roles["p3"]
    pending: dict[int, Transport] = {}
    suspend_kinds: dict[int, list] = {ch: [] for ch in alices}
    trigger = {"armed": False}

    def on_barrier(rnd):
        if trigger["armed"] and rnd >= 1:
            trigger["armed"] = False
            conn[ch0]["ta"].close()           # clean disconnect
            conn[ch1]["chaos"]._crash()       # dark peer: deadline path
        for ch in list(pending):
            if hub._peers[ch].suspended:
                suspend_kinds[ch].append(
                    classify_error(hub._peers[ch].suspend_err)
                )
                hub.resume_peer(ch, pending.pop(ch))

    hub.on_barrier = on_barrier

    def _mk(ch, fn):
        def call():
            try:
                return fn()
            except TransportError:
                pass
            raw_a, nh = InMemoryDuplex.pair()
            if ch == ch1:
                # the restarted dark peer re-arms its silent-crash wrapper
                chaos = ChaosTransport(raw_a, FaultPlan(crash_silent=True))
                conn[ch].update(ta=chaos, chaos=chaos)
                ta = chaos
            else:
                conn[ch].update(ta=raw_a, chaos=None)
                ta = raw_a
            pending[ch] = nh
            alices[ch].resume(ta)
            return alices[ch].resume_run()
        return call

    outcomes, results, errors = _drive_hub(
        hub, {ch: _mk(ch, ep.run) for ch, ep in alices.items()},
        join_timeout=120.0,
    )
    assert not errors, errors
    assert all(o.ok for o in outcomes.values())
    st = hub.stats
    uploads0 = st["store_uploads"]
    sess_ids = {ch: id(hub._peers[ch].sessions[0]) for ch in alices}
    resumes_expected = 0

    for e in range(1, epochs + 1):
        hub_muts: dict[int, dict] = {}
        alice_muts: dict[int, dict] = {}
        for ch, ep in alices.items():
            b_cur = hub._peers[ch].sessions[0].state.b
            hub_muts[ch] = {0: (_fresh_elems(rng, 24),
                                rng.permutation(b_cur)[:24])}
            a_base = ep.sessions[0].state.a
            alice_muts[ch] = {0: (_fresh_elems(rng, 6),
                                  rng.permutation(a_base)[:6])}
        hub.advance_epoch(hub_muts)
        for ch, ep in alices.items():
            ep.advance_epoch(alice_muts.get(ch, {}))

        crash = e in crash_epochs
        if crash:
            trigger["armed"] = True
            resumes_expected += 2

        outcomes, results, errors = _drive_hub(
            hub, {ch: _mk(ch, ep.run_epoch) for ch, ep in alices.items()},
            join_timeout=120.0,
        )
        st = hub.stats
        assert not errors, (e, errors)

        # zero store rebuilds, zero re-admissions, zero full re-syncs:
        # resumption re-binds to the resident sessions and stores
        assert st["store_builds"] == 0, (e, st)
        assert st["store_uploads"] == uploads0
        assert st.get("peers_failed", 0) == 0, (e, st)
        for ch in alices:
            assert id(hub._peers[ch].sessions[0]) == sess_ids[ch]

        if crash:
            assert outcomes[ch0].error_kind == "resumed", e
            assert outcomes[ch1].error_kind == "resumed", e
            assert suspend_kinds[ch0][-1] == "transport"
            assert suspend_kinds[ch1][-1] == "deadline"
        assert st["peers_resumed"] >= resumes_expected, (e, st)

        for ch, ep in alices.items():
            assert outcomes[ch].ok and outcomes[ch].verified == [True], (
                e, ch, outcomes[ch].error
            )
            a_e = ep.sessions[0].state.a
            b_e = hub._peers[ch].sessions[0].state.b
            r = results[ch][0]
            oracle = reconcile(a_e, b_e, cfgs[ch], d_known=dks[ch])
            if crash:
                assert oracle.rounds >= 2, "crash epoch must be multi-round"
            assert r.success and r.diff == oracle.diff == true_diff(a_e, b_e)
            assert r.rounds == oracle.rounds, (e, ch)
            assert r.bytes_per_round == oracle.bytes_per_round, (e, ch)
            assert r.bytes_sent == oracle.bytes_sent, (e, ch)
            assert r.estimator_bytes == oracle.estimator_bytes, (e, ch)

    st = hub.stats
    # every scripted crash-restart resumed; the scripted corruption (if
    # any) healed through one extra suspend->resume cycle
    extra = 1 if corrupt_op is not None else 0
    assert st["peers_resumed"] == resumes_expected + extra, st
    assert st["resume_replay_bytes"] <= _BARRIER_FRAME_BOUND * st["peers_resumed"]
    assert hub._peers[ch0].resumes == len(crash_epochs)
    assert hub._peers[ch1].resumes == len(crash_epochs)
    assert not hub.stale_channels

    # the random-chaos peer actually saw chaos and never crashed
    chaos2 = conn[ch2]["chaos"]
    assert chaos2 is not None and not chaos2.crashed
    assert chaos2.dropped > 0 and chaos2.duplicated > 0
    assert chaos2.reordered > 0
    if corrupt_op is not None:
        assert suspend_kinds[ch3] and suspend_kinds[ch3][-1] == "transport"
        assert hub._peers[ch3].resumes == 1
    return hub


def test_chaos_epochs_fast():
    """3 seeded epochs with the K=2 crash-restart in epoch 1: the
    blocking-tier variant of the chaos soak."""
    _chaos_soak(3, crash_epochs=(1,), seed=42)


@pytest.mark.slow
def test_chaos_soak_20_epochs():
    """The full acceptance soak: 20 epochs, two K=2 crash-restart epochs,
    persistent loss/dup/reorder chaos and a scripted mid-run corruption."""
    _chaos_soak(20, crash_epochs=(1, 8), corrupt_op=260, seed=7)


# ---------------------------------------------------------------------------
# tree-phase crashes (cold-start front end, DESIGN.md §15)
# ---------------------------------------------------------------------------


def _tree_pair(seed=23):
    """A pair whose walk is guaranteed multi-level (d > leaf_d); sorted
    unique, the form ``leaf_slices`` (and the walk itself) operates on."""
    a, b = make_pair(600, 120, np.random.default_rng(seed))
    return np.unique(a), np.unique(b), PBSConfig(seed=seed)


def test_mid_tree_crash_evicts_cleanly_then_fresh_channel_readmits():
    """A peer dying mid-walk is a hard eviction — the tree phase holds no
    resumption record, so even an armed resume window never suspends it —
    and the same client re-admits from scratch on a fresh channel."""
    from repro.tree import TreeConfig, partition_pair
    from repro.tree.partition import leaf_slices

    a, b, cfg = _tree_pair()
    _, stats = partition_pair(a, b, TreeConfig())
    assert stats.levels >= 2, "walk too shallow to crash mid-tree"

    t_a_raw, t_h = InMemoryDuplex.pair()
    t_a = ChaosTransport(t_a_raw, FaultPlan(crash_after_sends=1))
    hub = HubEndpoint(resume_window=30.0, recv_deadline=2.0)
    ch1 = hub.add_peer(t_h, label="treecrash")
    hub.submit_tree(ch1, b, cfg=cfg)
    ep1 = AliceEndpoint(t_a, channel=ch1)
    ep1.submit_tree(a, cfg)

    def drive():
        with pytest.raises(TransportError):
            ep1.run()

    th = threading.Thread(target=drive, daemon=True)
    th.start()
    outcomes = hub.serve()
    th.join(timeout=60)
    assert not th.is_alive()
    assert not outcomes[ch1].ok
    assert outcomes[ch1].error_kind == "transport"
    assert outcomes[ch1].tree_leaves is None     # walk never completed
    assert not hub._peers[ch1].suspended
    assert ch1 in hub.stale_channels
    st = hub.stats
    assert st["peers_resumed"] == 0
    assert st["peers_failed_by_kind"] == {"transport": 1}

    # the client reconnects on a brand-new channel and stages the tree
    # again: full admission, byte-identical to the in-process walk
    ta2, th2 = InMemoryDuplex.pair()
    ch2 = hub.add_peer(ta2 if False else th2, label="retry")
    hub.submit_tree(ch2, b, cfg=cfg)
    ep2 = AliceEndpoint(ta2, channel=ch2)
    ep2.submit_tree(a, cfg)
    state: dict = {}

    def drive2():
        state["res"] = ep2.run()

    th2d = threading.Thread(target=drive2, daemon=True)
    th2d.start()
    outcomes2 = hub.serve()
    th2d.join(timeout=60)
    assert not th2d.is_alive()
    assert outcomes2[ch2].ok
    assert outcomes2[ch2].tree_leaves == ep2.tree_leaves == len(
        partition_pair(a, b, TreeConfig())[0]
    )
    got = set().union(*(r.diff for r in state["res"].values()))
    leaves, _ = partition_pair(a, b, TreeConfig())
    want = set()
    for a_sub, b_sub, leaf in zip(
        leaf_slices(a, leaves), leaf_slices(b, leaves), leaves
    ):
        want |= reconcile(a_sub, b_sub, cfg, d_known=leaf.d_plan).diff
    assert got == want


def test_post_tree_crash_resumes_via_msg_resume():
    """Once the walk has settled into leaf PBS sessions, a crash is just
    an ordinary mid-protocol crash: the peer suspends at the barrier and
    resumes through MSG_RESUME with no re-walk and no re-admission."""
    from repro.tree import TreeConfig, partition_pair

    a, b, cfg = _tree_pair(seed=29)
    _, stats = partition_pair(a, b, TreeConfig())
    # alice's sends: one digest frame per level, then the PBS rounds —
    # crash on the second post-tree send, squarely inside the rounds
    crash_after = stats.levels + 1

    t_a_raw, t_h = InMemoryDuplex.pair()
    t_a = ChaosTransport(t_a_raw, FaultPlan(crash_after_sends=crash_after))
    hub = HubEndpoint(resume_window=30.0, recv_deadline=10.0)
    ch = hub.add_peer(t_h, label="latecrash")
    hub.submit_tree(ch, b, cfg=cfg)
    ep = AliceEndpoint(t_a, channel=ch)
    ep.submit_tree(a, cfg)

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
    assert not th.is_alive()
    assert "crash" in state, "scripted crash never fired"
    assert outcomes[ch].ok and outcomes[ch].error_kind == "resumed"
    assert ep.resumes == 1 and hub.stats["peers_resumed"] == 1
    assert hub.stats.get("peers_failed", 0) == 0
    # the walk itself never re-ran: one tree phase's worth of digest bytes
    assert outcomes[ch].tree_leaves == stats.leaves
    assert ep.wire_stats["tree_frame_bytes"] == stats.digest_bytes
    # every leaf session still byte-identical to its standalone oracle
    leaves, _ = partition_pair(a, b, TreeConfig())
    from repro.tree.partition import leaf_slices

    for sid, (a_sub, b_sub, leaf) in enumerate(
        zip(leaf_slices(a, leaves), leaf_slices(b, leaves), leaves)
    ):
        oracle = reconcile(a_sub, b_sub, cfg, d_known=leaf.d_plan)
        r = state["res"][sid]
        assert r.success and r.diff == oracle.diff
        assert r.bytes_sent == oracle.bytes_sent
        assert r.bytes_per_round == oracle.bytes_per_round
