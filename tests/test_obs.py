"""The observability layer (DESIGN.md §14): registry, tracing, export.

What's locked down here:

* the **schema contract** — `repro.obs.SCHEMA` is self-consistent, the
  DESIGN.md §14 table is generated from it and must match it *exactly*
  (name, kind, unit, owner, description), and `Recorder` rejects any
  undeclared key with `MetricsError`, so metric names cannot drift from
  the documentation;
* **derived-snapshot parity** — the legacy dict surfaces
  (`server.stats`, `hub.stats`, endpoint `wire_stats`) are rebuilt from
  the registry and must stay value-identical to the numbers queryable by
  dotted name, including under a seeded `ChaosTransport` run
  (`sessions_degraded`, `resume_replay_bytes`, `peers_failed_by_kind`);
* the **store-mark regression** — `submit()` after `run()` discards the
  finished batch *and* the recorder's store mark, so the next run's
  per-run store ledger diffs against the new batch's zeros instead of a
  dead batch's cumulative counters;
* **tracing acceptance** — a hub chaos run with one shared tracer
  produces a Chrome trace (Perfetto-loadable: every complete event
  carries ts/dur/pid/tid) showing per-peer round spans, ARQ
  retransmits, and a resume transition; both export formats round-trip
  through `load_events`; `tools/trace_report.py` summarizes occupancy,
  per-peer traffic, and the observed-vs-`core.markov` round histogram.
"""
import json
import pathlib
import re
import sys
import threading

import numpy as np
import pytest

from repro.core.pbs import PBSConfig, reconcile
from repro.core.simdata import make_pair
from repro.net import (
    AliceEndpoint,
    BobEndpoint,
    ChaosTransport,
    FaultPlan,
    HubEndpoint,
    InMemoryDuplex,
    ReliableTransport,
    TransportError,
    run_hub,
    run_pair,
)
from repro.obs import (
    NULL_TRACER,
    SCHEMA,
    MetricsError,
    Recorder,
    Tracer,
    current_tracer,
    load_events,
    set_tracer,
    use_tracer,
)
from repro.recon import ReconcileServer, engine
from repro.recon.session import SessionBatch

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tools"))

import trace_report  # noqa: E402


# ---------------------------------------------------------------------------
# schema contract
# ---------------------------------------------------------------------------


def test_schema_self_consistent():
    for name, spec in SCHEMA.items():
        assert spec.name == name
        assert name.startswith(spec.owner + ".")
        assert spec.key == name[len(spec.owner) + 1:]
        assert spec.desc


_ROW_RE = re.compile(
    r"^\| `([\w.]+)` \| (\w+) \| (\w+) \| (\w+) \| (.+?) \|$", re.MULTILINE
)


def test_design_section14_table_matches_schema_exactly():
    """The §14 table IS the schema: every metric row matches its
    MetricSpec field for field, with no extras on either side."""
    text = (ROOT / "DESIGN.md").read_text()
    sect = text.split("## §14", 1)
    assert len(sect) == 2, "DESIGN.md has no §14 section"
    rows = {m.group(1): m.groups()[1:] for m in _ROW_RE.finditer(sect[1])}
    assert set(rows) == set(SCHEMA), (
        f"table/schema drift: only in table {set(rows) - set(SCHEMA)}, "
        f"only in schema {set(SCHEMA) - set(rows)}"
    )
    for name, (kind, unit, owner, desc) in rows.items():
        spec = SCHEMA[name]
        assert (kind, unit, owner) == (spec.kind, spec.unit, spec.owner), name
        assert desc == spec.desc, name


def test_recorder_rejects_undeclared_keys():
    r = Recorder()
    with pytest.raises(MetricsError):
        r.inc("server.not_a_metric")
    with pytest.raises(MetricsError):
        r.set("nowhere.rounds", 1)
    with pytest.raises(MetricsError):
        r.publish("server", {"rounds": 1, "bogus_key": 2})
    # error inherits KeyError so existing dict-shaped handling still works
    assert issubclass(MetricsError, KeyError)


def test_recorder_basics_and_views():
    r = Recorder()
    r.inc("wire.retransmits")
    r.inc("wire.retransmits", 2)
    r.set("wire.rto_ms", 12.5)
    r.set("hub.peers_failed_by_kind", {"deadline": 1})
    r.inc("hub.peers_failed_by_kind", label="transport")
    assert r.value("wire.retransmits") == 3
    assert r.value("wire.rto_ms") == 12.5
    assert r.value("hub.peers_failed_by_kind") == {
        "deadline": 1, "transport": 1
    }
    assert r.value("hub.peers_failed_by_kind", label="deadline") == 1
    assert r.value("server.rounds", default=0) == 0
    view = r.view("wire")
    assert view["retransmits"] == 3 and view["rto_ms"] == 12.5
    # views hand out copies: mutating one can't corrupt the registry
    r.view("hub")["peers_failed_by_kind"]["deadline"] = 99
    assert r.value("hub.peers_failed_by_kind", label="deadline") == 1
    snap = r.snapshot()
    assert snap["wire.retransmits"] == 3


def test_recorder_marks():
    r = Recorder()
    r.mark("store", {"store_builds": 2, "store_delta_bytes": 100})
    d = r.delta_since_mark("store", {"store_builds": 5,
                                     "store_delta_bytes": 160})
    assert d == {"store_builds": 3, "store_delta_bytes": 60}
    r.drop_mark("store")
    d = r.delta_since_mark("store", {"store_builds": 5,
                                     "store_delta_bytes": 160})
    assert d == {"store_builds": 5, "store_delta_bytes": 160}
    r.drop_mark("store")   # idempotent on a missing mark


# ---------------------------------------------------------------------------
# derived snapshots: legacy dicts == registry values
# ---------------------------------------------------------------------------


def test_server_stats_is_registry_view():
    a, b = make_pair(600, 10, np.random.default_rng(0))
    srv = ReconcileServer()
    srv.submit(a, b, cfg=PBSConfig(seed=0), d_known=10)
    res = srv.run()[0]
    assert res.success
    st = srv.stats
    assert st == srv.recorder.view("server")
    assert srv.recorder.value("server.rounds") == st["rounds"]
    assert srv.recorder.value("server.h2d_ratio") == st["h2d_ratio"]
    # kernel retrace attribution flows into the kernels owner too
    assert srv.recorder.value("kernels.retraces_total") is not None
    by_fn = srv.recorder.value("kernels.retraces_by_fn")
    assert isinstance(by_fn, dict)


def test_submit_after_run_resets_store_mark():
    """Regression: a post-run ``submit`` discards the finished batch; the
    recorder's store mark must die with it, or the next run's store
    ledger diffs against the dead batch's counters (reporting 0 builds
    for a store that was just built)."""
    a, b = make_pair(600, 10, np.random.default_rng(0))
    srv = ReconcileServer()
    srv.submit(a, b, cfg=PBSConfig(seed=0), d_known=10)
    srv.run()
    assert srv.stats["store_builds"] >= 1

    a2, b2 = make_pair(600, 10, np.random.default_rng(1))
    sid = srv.submit(a2, b2, cfg=PBSConfig(seed=1), d_known=10)
    res = srv.run()[sid]
    oracle = reconcile(a2, b2, PBSConfig(seed=1), d_known=10)
    assert res.success and res.diff == oracle.diff
    st = srv.stats
    # the fresh batch built exactly one store (only the new session has
    # live work); the dead-mark bug reported 0 here
    assert st["store_builds"] == 1
    assert st["store_compactions"] == 0 and st["h2d_delta_bytes"] == 0


def _crash_resume_hub(tracer=None, arq_peer=False, seed=23):
    """Two-peer hub under seeded chaos: peer 0 crash-resumes, peer 1
    (optionally) lives behind a lossy seeded ARQ channel.  One shared
    tracer covers hub, endpoints, transports, and injectors."""
    rng = np.random.default_rng(seed)
    univ = rng.choice(1 << 20, size=3000, replace=False).astype(np.uint32)
    cfg_kw = dict(n_override=127, t_override=7, g_override=4)
    hub = HubEndpoint(resume_window=30.0, recv_deadline=10.0, tracer=tracer)
    alices, pending = {}, {}

    a0, b0 = univ[:2600], univ[400:]
    d0 = len(np.setxor1d(a0, b0))
    cfg0 = PBSConfig(seed=seed, **cfg_kw)
    raw0, th0 = InMemoryDuplex.pair()
    t0 = ChaosTransport(raw0, FaultPlan(crash_after_sends=1), tracer=tracer)
    ch0 = hub.add_peer(th0, label="crasher")
    hub.submit(ch0, b0, cfg=cfg0, d_known=d0)
    ep0 = AliceEndpoint(t0, channel=ch0, tracer=tracer)
    ep0.submit(a0, cfg=cfg0, d_known=d0)
    alices[ch0] = ep0
    oracles = {ch0: reconcile(a0, b0, cfg0, d_known=d0)}

    ch1 = None
    if arq_peer:
        a1, b1 = make_pair(700, 60, np.random.default_rng(seed + 1))
        cfg1 = PBSConfig(seed=seed + 1, **cfg_kw)
        raw1, rawh1 = InMemoryDuplex.pair()
        chaos1 = ChaosTransport(
            raw1, FaultPlan(seed=seed + 50, loss=0.15, dup=0.05),
            tracer=tracer,
        )
        t1 = ReliableTransport(chaos1, timeout=0.02, max_retries=400,
                               seed=1, tracer=tracer)
        th1 = ReliableTransport(rawh1, timeout=0.02, max_retries=400,
                                seed=101, tracer=tracer)
        ch1 = hub.add_peer(th1, label="lossy")
        hub.submit(ch1, b1, cfg=cfg1, d_known=60)
        ep1 = AliceEndpoint(t1, channel=ch1, tracer=tracer)
        ep1.submit(a1, cfg=cfg1, d_known=60)
        alices[ch1] = ep1
        oracles[ch1] = reconcile(a1, b1, cfg1, d_known=60)

    def on_barrier(rnd):
        if "t" in pending and hub._peers[ch0].suspended:
            hub.resume_peer(ch0, pending.pop("t"))

    hub.on_barrier = on_barrier

    def drive0():
        try:
            return alices[ch0].run()
        except TransportError:
            pass
        na, nh = InMemoryDuplex.pair()
        pending["t"] = nh
        alices[ch0].resume(na)
        return alices[ch0].resume_run()

    fns = {ch0: drive0}
    if ch1 is not None:
        fns[ch1] = alices[ch1].run
    state, threads = {}, []
    for ch, fn in fns.items():
        def runner(ch=ch, fn=fn):
            state[ch] = fn()
        t = threading.Thread(target=runner, daemon=True)
        threads.append(t)
        t.start()
    outcomes = hub.serve()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "peer thread leaked"
    for ch, oracle in oracles.items():
        res = state[ch][0]
        assert res.success and res.diff == oracle.diff
        assert res.bytes_sent == oracle.bytes_sent
    return hub, alices, outcomes, ch0, ch1


def test_chaos_registry_parity_with_legacy_stats():
    """Satellite: the chaos stats read through the registry match the
    legacy dicts exactly under a seeded ChaosTransport run."""
    hub, alices, outcomes, ch0, _ = _crash_resume_hub()
    st = hub.stats
    assert outcomes[ch0].error_kind == "resumed"
    assert st["peers_resumed"] == 1 and st["resume_replay_bytes"] > 0
    rec = hub.recorder
    for key in ("peers_resumed", "resume_replay_bytes", "sessions_degraded",
                "peers_failed", "peers_failed_by_kind", "rounds", "epoch"):
        assert rec.value(f"hub.{key}") == st[key], key
    # per-peer wire stats are registry views on the peer's own recorder
    hw = hub._peers[ch0].wire_stats()
    prec = hub._peers[ch0].recorder
    for key, val in hw.items():
        assert prec.value(f"wire.{key}") == val, key
    aw = alices[ch0].wire_stats
    arec = alices[ch0].recorder
    for key, val in aw.items():
        assert arec.value(f"wire.{key}") == val, key
    assert arec.value("endpoint.resumes") == alices[ch0].resumes == 1


def test_eviction_and_degradation_registry_parity():
    """peers_failed_by_kind and sessions_degraded hold registry/legacy
    parity on the eviction and degradation-ladder paths too."""
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
    hub.serve()
    th.join(timeout=60)
    st = hub.stats
    assert st["peers_failed_by_kind"] == {"transport": 1}
    assert hub.recorder.value("hub.peers_failed_by_kind") == {"transport": 1}
    assert hub.recorder.value("hub.peers_failed") == st["peers_failed"] == 1

    # degradation ladder: hopeless d̂ = 250 against d = 1000, budget 2
    rngd = np.random.default_rng(11)
    univ = rngd.choice(1 << 20, size=4000, replace=False).astype(np.uint32)
    th_a, th_h = InMemoryDuplex.pair()
    dhub = HubEndpoint(degrade=True, recv_deadline=30.0)
    dcfg = PBSConfig(seed=5, max_rounds=2)
    dch = dhub.add_peer(th_h)
    dhub.submit(dch, univ[500:], cfg=dcfg, d_known=250)
    dep = AliceEndpoint(th_a, channel=dch, degrade=True)
    dep.submit(univ[:3500], cfg=dcfg, d_known=250)
    _, dresults, derrors = run_hub(dhub, {dch: dep})
    assert not derrors and dresults[dch][0].success
    dst = dhub.stats
    assert dst["sessions_degraded"] >= 1
    assert dhub.recorder.value("hub.sessions_degraded") == dst["sessions_degraded"]
    dep.wire_stats    # the endpoint.* freeze point
    assert dep.recorder.value("endpoint.sessions_degraded") == dep.sessions_degraded


# ---------------------------------------------------------------------------
# tracing: spans, exports, acceptance trace
# ---------------------------------------------------------------------------


def test_null_tracer_is_inert_and_shared():
    assert NULL_TRACER.enabled is False
    s1 = NULL_TRACER.span("x", cat="device", anything=1)
    s2 = NULL_TRACER.annotate("y")
    with s1:
        pass
    NULL_TRACER.instant("z")
    NULL_TRACER.counter("c", 1)
    assert s1 is s2    # one shared no-op context manager, zero allocation


def test_tracer_span_structure():
    tr = Tracer()
    with tr.span("outer", cat="host", k=1):
        with tr.span("inner", cat="device"):
            pass
    tr.instant("mark", v=2)
    tr.counter("gauge", 7)
    evs = tr.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["ph"] == "X" and by_name["outer"]["args"] == {"k": 1}
    assert by_name["inner"]["cat"] == "device"
    # inner closed first and nests within outer on the timeline
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert (by_name["inner"]["ts"] + by_name["inner"]["dur"]
            <= by_name["outer"]["ts"] + by_name["outer"]["dur"] + 1e-6)
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["s"] == "t"
    assert by_name["gauge"]["ph"] == "C"
    assert by_name["thread_name"]["ph"] == "M"
    assert all(e["pid"] == 1 for e in evs)


def test_set_tracer_and_use_tracer_install_and_remove():
    assert current_tracer() is NULL_TRACER
    tr, inner = Tracer(), Tracer()
    set_tracer(tr)
    try:
        assert current_tracer() is tr
        with use_tracer(inner) as got:
            assert got is inner and current_tracer() is inner
        assert current_tracer() is tr        # the previous one comes back
    finally:
        set_tracer(None)
    assert current_tracer() is NULL_TRACER
    with pytest.raises(RuntimeError):
        with use_tracer(tr):
            raise RuntimeError("boom")
    assert current_tracer() is NULL_TRACER   # restored on an exception too


def _components():
    """(name, tracer) of one of each component built without ``tracer=``."""
    raw_a, raw_b = InMemoryDuplex.pair()
    return [
        ("hub", HubEndpoint().tracer),
        ("hub batch", HubEndpoint()._batch.tracer),
        ("alice", AliceEndpoint(raw_a).tracer),
        ("bob", BobEndpoint(raw_b).tracer),
        ("server", ReconcileServer().tracer),
        ("batch", SessionBatch([]).tracer),
        ("arq", ReliableTransport(raw_a)._tracer),
        ("chaos", ChaosTransport(raw_a, FaultPlan())._tracer),
    ]


def test_components_pick_up_the_installed_tracer():
    for name, tracer in _components():
        assert tracer is NULL_TRACER, name
    tr = Tracer()
    with use_tracer(tr):
        for name, tracer in _components():
            assert tracer is tr, name
        # an explicit tracer= still wins over the installed one
        mine = Tracer()
        assert HubEndpoint(tracer=mine).tracer is mine
        assert ReconcileServer(tracer=mine).tracer is mine
    for name, tracer in _components():
        assert tracer is NULL_TRACER, name


def test_set_dispatch_tracer_is_the_process_tracer():
    tr = Tracer()
    engine.set_dispatch_tracer(tr)
    try:
        assert current_tracer() is tr
        assert HubEndpoint().tracer is tr
    finally:
        engine.set_dispatch_tracer(None)
    assert current_tracer() is NULL_TRACER
    assert not hasattr(engine, "_DISPATCH_TRACER")


def test_exports_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("a"):
        pass
    tr.instant("b", x=1)
    chrome = tmp_path / "t.json"
    jsonl = tmp_path / "t.jsonl"
    n1 = tr.export_chrome(chrome)
    n2 = tr.export_jsonl(jsonl)
    assert n1 == n2 == len(tr.events())
    assert load_events(chrome) == load_events(jsonl) == tr.events()
    doc = json.loads(chrome.read_text())
    assert doc["displayTimeUnit"] == "ms"


def test_arq_retransmit_instrumentation():
    """A seeded partition drops the first datagram: the ARQ layer
    retransmits and the tracer records it, seq- and attempt-tagged."""
    tr = Tracer()
    raw_a, raw_b = InMemoryDuplex.pair()
    chaos = ChaosTransport(raw_a, FaultPlan(partitions=((0, 1),)), tracer=tr)
    ta = ReliableTransport(chaos, timeout=0.02, max_retries=50, tracer=tr)
    tb = ReliableTransport(raw_b, timeout=0.02, max_retries=50)
    got = {}

    def receiver():
        got["data"] = tb.recv(timeout=5.0)

    th = threading.Thread(target=receiver, daemon=True)
    th.start()
    ta.send(b"payload")
    th.join(timeout=10)
    assert got.get("data") == b"payload"
    assert ta.retransmits >= 1
    names = [e["name"] for e in tr.events()]
    assert "chaos.drop" in names
    retrans = [e for e in tr.events() if e["name"] == "arq.retransmit"]
    assert len(retrans) == ta.retransmits
    assert retrans[0]["args"]["attempt"] >= 1
    sends = [e for e in tr.events() if e["name"] == "arq.send"]
    assert sends and sends[0]["cat"] == "arq" and "dur" in sends[0]


def test_hub_chaos_trace_acceptance(tmp_path):
    """The ISSUE acceptance trace: ONE shared tracer across a hub chaos
    run exports a Perfetto-loadable Chrome trace showing per-peer round
    spans, ARQ retransmits, and a resume transition."""
    tr = Tracer()
    hub, alices, outcomes, ch0, ch1 = _crash_resume_hub(
        tracer=tr, arq_peer=True)
    assert outcomes[ch0].error_kind == "resumed"
    assert outcomes[ch1].ok

    path = tmp_path / "chaos_trace.json"
    n = tr.export_chrome(path)
    evs = load_events(path)
    assert len(evs) == n > 0
    names = {e["name"] for e in evs}

    # per-peer round spans, attributed by peer label and channel
    replies = [e for e in evs if e["name"] == "peer.round.reply"]
    assert {e["args"]["peer"] for e in replies} == {"crasher", "lossy"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in replies)
    # ARQ retransmits fired on the lossy peer and were recorded
    retrans = sum(ep.wire_stats.get("retransmits", 0)
                  for ep in alices.values())
    assert retrans >= 1
    assert "arq.retransmit" in names
    # the resume transition, both sides
    assert "peer.suspend" in names and "peer.resume" in names
    assert "resume" in names           # the Alice-side span
    assert "chaos.crash" in names
    # Perfetto-loadable: a JSON object document, complete events carry
    # ts/dur/pid/tid, instants are scoped, metadata names the threads
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    for e in doc["traceEvents"]:
        assert "name" in e and "ph" in e and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e
        if e["ph"] == "i":
            assert e["s"] == "t"
    assert sum(e["ph"] == "M" for e in doc["traceEvents"]) >= 2  # threads


# ---------------------------------------------------------------------------
# trace_report
# ---------------------------------------------------------------------------


def test_round_apply_outcomes_span_counts():
    """Alice's one span per round over the outcome step: its counts of
    recovered values, fake rejections and split-filtered slots add up to
    the session's result."""
    a, b = make_pair(2000, 60, np.random.default_rng(4))
    cfg = PBSConfig(seed=1, n_override=63, t_override=3, g_override=4)
    ta, tb = InMemoryDuplex.pair()
    tr = Tracer()
    alice, bob = AliceEndpoint(ta, tracer=tr), BobEndpoint(tb)
    alice.submit(a, cfg=cfg, d_known=60)
    bob.submit(b, cfg=cfg, d_known=60)
    (res,) = run_pair(alice, bob).values()
    assert res.success and res.decode_failures
    spans = [e["args"] for e in tr.events() if e["name"] == "round.apply_outcomes"]
    assert [s["round"] for s in spans] == list(range(1, res.rounds + 1))
    assert {s["sessions"] for s in spans} == {1}
    assert sum(s["recovered"] for s in spans) == len(res.diff)
    assert sum(s["fake_rejections"] for s in spans) == res.fake_rejections
    assert spans[0]["filtered_slots"] == 0 < sum(s["filtered_slots"] for s in spans)


def test_trace_report_sections(tmp_path):
    tr = Tracer()
    srv = ReconcileServer(tracer=tr)
    for s in range(4):
        a, b = make_pair(600, 10, np.random.default_rng(s))
        srv.submit(a, b, cfg=PBSConfig(seed=s), d_known=10)
    results = srv.run()
    assert all(r.success for r in results.values())
    path = tmp_path / "t.json"
    tr.export_chrome(path)

    rep = trace_report.build_report(load_events(path))
    occ = rep["occupancy"]
    assert occ, "no occupancy rows"
    row = next(iter(occ.values()))
    assert row["device_wait_ms"] > 0
    assert row["wall_ms"] >= row["device_wait_ms"]
    assert 0 < row["device_wait_frac"] <= 1
    # device wait is the readback spans alone, not their host-work parents
    waits = [e for e in tr.events() if e["name"] == "device.readback"]
    assert waits
    assert row["device_wait_ms"] <= sum(e["dur"] for e in waits) / 1e3 + 1e-9

    peers = rep["peers"]
    assert peers["local"]["sessions"] == 4
    assert peers["local"]["diff"] == sum(len(r.diff) for r in results.values())
    assert peers["local"]["bytes"] == sum(r.bytes_sent
                                          for r in results.values())

    hist = rep["round_histogram"]
    assert hist, "no parameter classes in the histogram"
    h = hist[0]
    assert sum(h["rounds_hist"]) == h["sessions"] == 4
    assert "markov_round_fracs" in h
    assert abs(sum(h["markov_round_fracs"]) - 1.0) < 0.1

    # the CLI wrapper runs on the same file
    assert trace_report.main([str(path)]) == 0
    assert trace_report.main([str(path), "--json"]) == 0


def test_trace_report_empty_trace_fails(tmp_path):
    path = tmp_path / "empty.jsonl"
    path.write_text("")
    assert trace_report.main([str(path)]) == 1
