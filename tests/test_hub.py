"""Multi-peer hub acceptance: N concurrent peers against one HubEndpoint.

The acceptance scenario (ISSUE 4): ≥ 8 concurrent peers — mixed known-d and
estimator sessions, one straggler that goes silent mid-protocol, one peer
that disconnects mid-protocol — over both the in-memory duplex and real TCP
loopback sockets.  Every *surviving* peer's results must be byte-identical
to ``core.pbs.reconcile`` (diff, measured per-round wire ledger, counters),
the straggler and the disconnector must fail with clean per-peer
``TransportError`` outcomes without perturbing anyone else, and the hub's
``stats`` must show the fusion contract: one store upload per cohort and
2 kernel launches + 1 decode launch per cohort-round, shared across peers.
"""
import threading

import numpy as np
import pytest

from repro.core.pbs import PBSConfig, plan_from_d_known, reconcile, true_diff
from repro.core.simdata import make_pair, make_pair_two_sided
from repro.net import (
    AliceEndpoint,
    HubEndpoint,
    InMemoryDuplex,
    Transport,
    TransportError,
    run_hub,
    tcp_loopback_pair,
)
from repro.obs import Tracer, use_tracer


class _SilentAfterPhase0(AliceEndpoint):
    """A straggler: completes submission/phase 0, then never sends a round
    frame — the hub's round barrier must evict it at the deadline while the
    other peers' round proceeds."""

    def run(self):
        self._phase0()
        return {}


class _CloseAfter(Transport):
    """Disconnect injection: pass through ``n_sends`` frames, then close the
    underlying transport and fail — a peer vanishing mid-protocol."""

    def __init__(self, inner: Transport, n_sends: int):
        super().__init__()
        self._inner = inner
        self._left = n_sends

    def send(self, data: bytes) -> None:
        if self._left <= 0:
            self._inner.close()
            raise TransportError("simulated mid-protocol disconnect")
        self._left -= 1
        self._inner.send(data)

    def recv(self, timeout: float | None = None) -> bytes:
        return self._inner.recv(timeout)

    def close(self) -> None:
        self._inner.close()

    @property
    def bytes_out(self) -> int:  # type: ignore[override]
        return self._inner.bytes_out

    @property
    def bytes_in(self) -> int:  # type: ignore[override]
        return self._inner.bytes_in

    @bytes_out.setter
    def bytes_out(self, v):  # Transport.__init__ assigns 0
        pass

    @bytes_in.setter
    def bytes_in(self, v):
        pass


def _transport_pairs(kind: str, n: int):
    """n (alice_side, hub_side) transport pairs of the requested kind."""
    if kind == "memory":
        return [InMemoryDuplex.pair() for _ in range(n)]
    return [tcp_loopback_pair() for _ in range(n)]


@pytest.mark.parametrize(
    "kind",
    # the in-memory variant covers the protocol fast; the real-socket
    # variant (the single heaviest fast-tier test) moves to the full-suite
    # job — CI's wire-endpoints job exercises loopback end-to-end anyway
    ["memory", pytest.param("loopback", marks=pytest.mark.slow)],
)
def test_hub_eight_peers_acceptance(kind):
    rng_seed = 100
    pairs = _transport_pairs(kind, 8)
    hub = HubEndpoint(recv_deadline=20.0)
    alices: dict[int, AliceEndpoint] = {}
    cases: dict[int, tuple] = {}

    # peers 1-6: healthy, mixed known-d / estimator / two-sided / overload
    specs = [
        (make_pair(700, 5, np.random.default_rng(rng_seed)),
         PBSConfig(seed=1), 5),
        (make_pair(800, 12, np.random.default_rng(rng_seed + 1)),
         PBSConfig(seed=2), 12),
        (make_pair(900, 10, np.random.default_rng(rng_seed + 2)),
         PBSConfig(seed=3), None),                       # estimator
        (make_pair_two_sided(800, 8, 6, np.random.default_rng(rng_seed + 3)),
         PBSConfig(seed=4), 14),
        (make_pair(1000, 20, np.random.default_rng(rng_seed + 4)),
         PBSConfig(seed=5), None),                       # estimator
        (make_pair(1200, 40, np.random.default_rng(rng_seed + 5)),
         PBSConfig(seed=6, n_override=255, t_override=8, g_override=1), 40),
    ]
    for i, ((a, b), cfg, dk) in enumerate(specs):
        ta, tb = pairs[i]
        ch = hub.add_peer(tb)
        hub.submit(ch, b, cfg=cfg, d_known=dk)
        ep = AliceEndpoint(ta, channel=ch)
        ep.submit(a, cfg=cfg, d_known=dk)
        alices[ch] = ep
        cases[ch] = (a, b, cfg, dk)

    # peer 7: straggler (estimator phase 0 completes, then silence)
    a7, b7 = make_pair(800, 9, np.random.default_rng(rng_seed + 6))
    ta7, tb7 = pairs[6]
    ch7 = hub.add_peer(tb7, label="straggler")
    hub.submit(ch7, b7, cfg=PBSConfig(seed=7))
    ep7 = _SilentAfterPhase0(ta7, channel=ch7)
    ep7.submit(a7, cfg=PBSConfig(seed=7))
    alices[ch7] = ep7

    # peer 8: disconnects mid-protocol (after its round-1 sketches frame,
    # before its outcome frame)
    a8, b8 = make_pair(800, 8, np.random.default_rng(rng_seed + 7))
    ta8, tb8 = pairs[7]
    ch8 = hub.add_peer(tb8, label="dropper")
    hub.submit(ch8, b8, cfg=PBSConfig(seed=8), d_known=8)
    ep8 = AliceEndpoint(_CloseAfter(ta8, n_sends=1), channel=ch8)
    ep8.submit(a8, cfg=PBSConfig(seed=8), d_known=8)
    alices[ch8] = ep8

    outcomes, results, errors = run_hub(hub, alices)

    # every surviving peer: byte-identical to the single-pair oracle
    for ch, (a, b, cfg, dk) in cases.items():
        exp = reconcile(a, b, cfg, d_known=dk)
        got = results[ch][0]
        assert got.diff == exp.diff == true_diff(a, b), ch
        assert got.bytes_per_round == exp.bytes_per_round, ch
        assert got.bytes_sent == exp.bytes_sent, ch
        assert got.estimator_bytes == exp.estimator_bytes, ch
        assert got.rounds == exp.rounds, ch
        assert got.success == exp.success, ch
        assert got.decode_failures == exp.decode_failures, ch
        assert got.fake_rejections == exp.fake_rejections, ch
        assert outcomes[ch].ok and outcomes[ch].verified == [True], ch
    # the overload peer really exercised the 3-way split through the hub
    overload_ch = list(cases)[5]
    assert results[overload_ch][0].decode_failures >= 1

    # straggler: evicted at the barrier deadline, sessions failed, clean error
    assert not outcomes[ch7].ok
    assert isinstance(outcomes[ch7].error, TransportError)
    assert all(s.failed for s in outcomes[ch7].sessions)

    # disconnector: clean per-peer TransportError, Alice side failed too
    assert not outcomes[ch8].ok
    assert isinstance(outcomes[ch8].error, TransportError)
    assert isinstance(errors[ch8], TransportError)
    assert ch7 in hub.stale_channels and ch8 in hub.stale_channels

    # fusion ledger: one store upload per cohort that ever went live, and
    # fused launches (2 encode kernels + 1 decode) per cohort-round shared
    # across all peers
    st = hub.stats
    live_keys = {
        s.code_key
        for ch in list(cases) + [ch8]     # ch8 was live at round-1 planning
        for s in outcomes[ch].sessions
    }
    assert st["store_uploads"] == len(live_keys), (st, live_keys)
    assert st["kernel_launches"] == 2 * st["cohort_rounds"]
    assert st["decode_launches"] == st["cohort_rounds"]
    # fusion really shared launches: strictly fewer cohort-rounds than the
    # sum of every surviving peer's own (rounds x cohorts) would be
    per_peer_rounds = sum(results[ch][0].rounds for ch in cases)
    assert st["cohort_rounds"] < per_peer_rounds


def test_hub_peer_joining_between_rounds_is_byte_identical():
    """A peer admitted after global round 1 must reconcile byte-identically
    to a pair that started alone (local round numbering via rnd0)."""
    hub = HubEndpoint(recv_deadline=30.0)
    a1, b1 = make_pair(1500, 40, np.random.default_rng(17))
    cfg1 = PBSConfig(seed=6, n_override=255, t_override=8, g_override=1)
    ta, tb = InMemoryDuplex.pair()
    ch1 = hub.add_peer(tb)
    hub.submit(ch1, b1, cfg=cfg1, d_known=40)
    ep1 = AliceEndpoint(ta, channel=ch1)
    ep1.submit(a1, cfg=cfg1, d_known=40)

    a2, b2 = make_pair(900, 10, np.random.default_rng(23))
    cfg2 = PBSConfig(seed=29)
    joined: dict = {}

    def on_barrier(rnd):
        if rnd == 1 and not joined:
            ta2, tb2 = InMemoryDuplex.pair()
            ch = hub.add_peer(tb2, label="late")
            hub.submit(ch, b2, cfg=cfg2, d_known=10)
            ep = AliceEndpoint(ta2, channel=ch)
            ep.submit(a2, cfg=cfg2, d_known=10)
            res: dict = {}
            th = threading.Thread(
                target=lambda: res.update(r=ep.run()), daemon=True
            )
            th.start()
            joined.update(ch=ch, th=th, res=res)

    hub.on_barrier = on_barrier
    outcomes, results, errors = run_hub(hub, {ch1: ep1})
    joined["th"].join(60)
    assert not errors and "r" in joined["res"]

    exp1 = reconcile(a1, b1, cfg1, d_known=40)
    assert results[ch1][0].diff == exp1.diff
    assert results[ch1][0].bytes_per_round == exp1.bytes_per_round

    ch2 = joined["ch"]
    exp2 = reconcile(a2, b2, cfg2, d_known=10)
    got2 = joined["res"]["r"][0]
    assert got2.diff == exp2.diff == true_diff(a2, b2)
    assert got2.bytes_per_round == exp2.bytes_per_round
    assert got2.rounds == exp2.rounds
    assert outcomes[ch2].ok and outcomes[ch2].verified == [True]
    assert outcomes[ch2].sessions[0].rnd0 >= 1  # really joined mid-run


def test_hub_outage_code_matches_oracle():
    """The outage cell's code through the served hub: 3 peers of 2·10^4
    keys per side at d = 1%, pinned to the (511, 10) BCH code over GF(2^9)
    that the planner picks at d = 10^4.  Every result equals
    ``core.pbs.reconcile`` field for field."""
    keys, d = 20_000, 200
    hub = HubEndpoint(recv_deadline=60.0)
    alices, cases = {}, {}
    for i in range(3):
        a, b = make_pair_two_sided(keys, d // 2, d - d // 2,
                                   np.random.default_rng(500 + i))
        cfg = PBSConfig(seed=90 + i, n_override=511, t_override=10)
        ta, tb = InMemoryDuplex.pair()
        ch = hub.add_peer(tb)
        hub.submit(ch, b, cfg=cfg, d_known=d)
        ep = AliceEndpoint(ta, channel=ch)
        ep.submit(a, cfg=cfg, d_known=d)
        alices[ch] = ep
        cases[ch] = (a, b, cfg)
    outcomes, results, errors = run_hub(hub, alices)
    assert not errors
    for ch, (a, b, cfg) in cases.items():
        exp = reconcile(a, b, cfg, d_known=d)
        got = results[ch][0]
        assert (got.n, got.t) == (exp.n, exp.t) == (511, 10)
        assert got.diff == exp.diff == true_diff(a, b), ch
        assert got.rounds == exp.rounds, ch
        assert got.bytes_per_round == exp.bytes_per_round, ch
        assert got.bytes_sent == exp.bytes_sent, ch
        assert got.success and exp.success, ch
        assert got.decode_failures == exp.decode_failures, ch
        assert outcomes[ch].ok and outcomes[ch].verified == [True], ch


def test_hub_rejects_wrong_and_stale_channel_ids():
    """A frame tagged with any channel other than the peer's own — unknown,
    someone else's, or a retired (stale) one — evicts only that peer."""
    from repro.wire import frames as wf

    # wrong id on the wire -> strict rejection at the frame layer
    hub = HubEndpoint(recv_deadline=2.0)
    ta, tb = InMemoryDuplex.pair()
    ch = hub.add_peer(tb)
    a, b = make_pair(400, 4, np.random.default_rng(5))
    hub.submit(ch, b, cfg=PBSConfig(seed=3), d_known=4)
    inner = wf.encode_tow_sketch(np.zeros(128, np.int64), 400)
    ta.send(wf.encode_mux(ch + 17, inner))
    out = hub.serve()
    assert not out[ch].ok
    assert "channel" in str(out[ch].error)
    assert ch in hub.stale_channels

    # a healthy retired peer's channel is stale too (never reused)
    hub2 = HubEndpoint(recv_deadline=30.0)
    ta2, tb2 = InMemoryDuplex.pair()
    ch2 = hub2.add_peer(tb2)
    hub2.submit(ch2, b, cfg=PBSConfig(seed=3), d_known=4)
    ep = AliceEndpoint(ta2, channel=ch2)
    ep.submit(a, cfg=PBSConfig(seed=3), d_known=4)
    outcomes, results, errors = run_hub(hub2, {ch2: ep})
    assert outcomes[ch2].ok and not errors
    assert ch2 in hub2.stale_channels
    # and a later add_peer never hands the id out again
    ta3, tb3 = InMemoryDuplex.pair()
    assert hub2.add_peer(tb3) != ch2


def test_unmultiplexed_frame_on_channel_stream_rejected():
    """A bare (non-mux) frame on a channel-tagged stream is a WireError on
    the receiving side — peers cannot bypass the envelope."""
    from repro.wire import frames as wf
    from repro.wire.frames import WireError
    from repro.net.transport import FrameStream

    ta, tb = InMemoryDuplex.pair()
    stream = FrameStream(tb, channel=1)
    ta.send(wf.encode_dhat(7))            # no envelope
    with pytest.raises(WireError, match="unmultiplexed"):
        stream.recv(timeout=1.0)
    # and a correctly tagged frame round-trips
    ta.send(wf.encode_mux(1, wf.encode_dhat(7)))
    msg_type, payload = stream.recv(timeout=1.0)
    assert msg_type == wf.MSG_DHAT and wf.decode_dhat(payload) == 7


def _nested(child: dict, parent: dict) -> bool:
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_hub_trace_spans_cover_and_nest():
    """Under a process-wide tracer, a 3-peer rejoin records the hub's
    set-up, admission, planning and readback spans: on the hub's thread
    they nest properly, ``hub.serve`` and ``hub.join_peers`` cover
    everything after the submits, and ``device.readback`` is the only
    ``cat="device"`` span."""
    pairs = [make_pair(700, 12, np.random.default_rng(40 + i)) for i in range(3)]
    tr = Tracer()
    with use_tracer(tr):
        hub = HubEndpoint(recv_deadline=30.0)
        alices = {}
        for i, (a, b) in enumerate(pairs):
            cfg = PBSConfig(seed=70 + i)
            ta, tb = InMemoryDuplex.pair()
            ch = hub.add_peer(tb, label=f"p{i}")
            hub.submit(ch, b, cfg=cfg, d_known=12)
            ep = AliceEndpoint(ta, channel=ch)
            ep.submit(a, cfg=cfg, d_known=12)
            alices[ch] = ep
        outcomes, results, errors = run_hub(hub, alices)
    assert not errors and all(o.ok for o in outcomes.values())
    for ch, (a, b) in zip(alices, pairs):
        assert results[ch][0].diff == true_diff(a, b)

    main = threading.get_ident()
    spans = [e for e in tr.events() if e["ph"] == "X"]
    mine = [e for e in spans if e["tid"] == main]
    by = {}
    for e in mine:
        by.setdefault(e["name"], []).append(e)
    for name in ("endpoint.submit", "hub.submit", "hub.serve", "hub.admit",
                 "session.state", "session.group_view", "session.member_set",
                 "hub.plan_round", "store.build", "store.layout",
                 "store.upload", "device.readback", "decode.reply_units",
                 "hub.join_peers"):
        assert name in by, name
    assert len(by["endpoint.submit"]) == len(by["hub.submit"]) == 3
    assert by["hub.admit"][0]["args"] == {"peers": 3, "sessions": 3}

    # the hub thread's spans form a tree: any two are disjoint or nested
    for i, x in enumerate(mine):
        for y in mine[i + 1:]:
            disjoint = (x["ts"] + x["dur"] <= y["ts"]
                        or y["ts"] + y["dur"] <= x["ts"])
            assert disjoint or _nested(x, y) or _nested(y, x), (x, y)

    def inside(child: str, parent: str) -> None:
        for c in by[child]:
            assert any(_nested(c, p) for p in by[parent]), (child, parent)

    inside("store.layout", "store.build")
    inside("store.upload", "store.build")
    inside("store.build", "hub.plan_round")
    inside("hub.plan_round", "hub.serve")
    inside("hub.admit", "hub.serve")
    inside("device.readback", "hub.serve")
    inside("decode.reply_units", "hub.decode")
    inside("session.group_view", "session.state")
    inside("session.member_set", "session.state")
    # the set-up names the id width its stable sort ran over: a radix sort
    # of uint8 ids up to 256 groups, of uint16 up to 65,536
    gs = {plan_from_d_known(PBSConfig(seed=70 + i), 12).g for i in range(3)}
    want = {8 if g <= 256 else 16 if g <= 65_536 else 64 for g in gs}
    assert {s["args"]["key_bits"] for s in by["session.group_view"]} == want
    # every A on the served path arrives sorted: membership reuses it, no copy
    assert all(s["args"]["sorted"] is True for s in by["session.member_set"])
    # each session state is built inside a peer's submit or the admission
    for s in by["session.state"]:
        assert any(_nested(s, p) for p in by["endpoint.submit"] + by["hub.admit"])
    assert {s["args"]["side"] for s in by["session.state"]} == {"a", "b"}
    assert all(r["args"]["what"] in ("encode", "decode")
               for r in by["device.readback"])
    # every readback says what it copied: round 1's encode brings back
    # each unit's n XOR words at least
    assert all(r["args"]["bytes"] > 0 for r in by["device.readback"])
    # (3 sessions of 2 units at n = 63 in round 1)
    assert max(r["args"]["bytes"] for r in by["device.readback"]) >= 4 * 63 * 6
    # the reply loop covers every unit of its cohort that was decoded
    assert {r["args"]["n"] for r in by["decode.reply_units"]} == {63}
    assert by["decode.reply_units"][0]["args"]["units"] == 6

    # after the last submit, serve and the joins cover the run but for
    # starting the peer threads (a few ms while they take the GIL)
    last_submit = max(e["ts"] + e["dur"] for e in by["endpoint.submit"])
    serve, join = by["hub.serve"][0], by["hub.join_peers"][0]
    end = join["ts"] + join["dur"]
    uncovered = (serve["ts"] - last_submit) + (join["ts"] - serve["ts"] - serve["dur"])
    assert serve["ts"] >= last_submit and join["ts"] >= serve["ts"] + serve["dur"]
    assert uncovered <= max(0.1 * (end - last_submit), 50e3)    # µs

    # device wait is named by the readback span alone, on every thread
    assert {e["name"] for e in spans if e["cat"] == "device"} == {"device.readback"}
    peer_reads = [e for e in spans if e["tid"] != main and e["name"] == "device.readback"]
    assert peer_reads           # the peers' own encodes, on their threads
