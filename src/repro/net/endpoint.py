"""The initiating PBS endpoint and the round helpers both wire sides share.

``AliceEndpoint`` holds the A sets, runs phase 0 (ToW sketch out, d_hat
numerator back), encodes her per-unit BCH sketches each round through the
single-side cohort executor (``recon.engine.encode_side`` over her
device-resident ``SessionBatch(sides=("a",))`` stores), applies the shared
``core.pbs.apply_round_outcomes`` to the serving side's reply frames, and
ships the checksum verdicts back as outcome frames.

The serving side is one state machine, ``repro.net.hub.HubEndpoint``; the
pair's ``BobEndpoint`` is that hub holding one peer whose frames carry no
``MSG_MUX`` envelope.  It mirrors the session/unit state machine from the
frames alone — its own decode failures drive ``queue_split`` exactly like
Alice's, and her outcome frames supply the checksum-settled flags it cannot
compute (it never sees A) — through the serving helpers defined here
(``serve_phase0``, ``serve_epoch_frame``, ``serve_tree_frame``,
``decode_side_b_round``, ``verify_ack_entries``).

Byte ledgers are *measured*: every ``bytes_per_round`` entry an endpoint
reports is derived from the frames that crossed the transport (via the
``repro.wire`` ledger-bit helpers on decoded content), then asserted equal
to the Formula-(1) accounting the in-process oracle computes — so
``ReconcileResult.bytes_sent`` from this path is a wire measurement that
happens to equal ``core.pbs.reconcile``'s ledger exactly.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from repro.core.hashing import derive_seed
from repro.core.pbs import (
    MAX_PARITY_EXTENSIONS,
    PBSConfig,
    ReconcileResult,
    apply_round_outcomes,
    checksum,
    effective_set,
    finalize_result,
    new_session_state,
    parity_extension_t,
    plan_from_d_known,
    plan_from_estimate,
)
from repro.core.tow import (
    ESTIMATE_LIMIT_FRAC,
    check_estimate,
    estimate_numerator,
    planned_d,
    tow_sketches,
)
from repro.kernels.ops import bch_decode_batched
from repro.kernels.platform import enable_persistent_cache
from repro.obs import Recorder, current_tracer
from repro.recon.engine import encode_side, encode_side_ext, readback
from repro.recon.session import (
    CohortRoundPlan,
    ReconSession,
    SessionBatch,
    advance_session,
    apply_churn,
    degrade_exhausted,
)
from repro.tree.partition import (
    SPAN,
    TreeConfig,
    TreeLeaf,
    leaf_slices,
    level_digests,
    level_verdicts,
    split_ranges,
)
from repro.wire import frames as wf
from repro.wire.frames import ReplyUnit, WireError
from repro.wire.varint import framed_len

from .transport import FrameStream, Transport

_EMPTY = np.zeros(0, dtype=np.uint32)

_ROUND_ARRAY_KEYS = (
    "row_map", "unit_valid", "seeds", "removed", "removed_cnt",
    "added", "added_cnt", "fseeds", "fbins", "fcnt",
)


@dataclass
class _SessionRows:
    """One live session's slice of its cohort's device outputs this round."""

    sess: ReconSession
    active: list
    bin_seed: int
    sk: np.ndarray        # (U, t) syndromes
    xors: np.ndarray      # (U, n) uint32 bin XOR folds
    csum: np.ndarray      # (U,) uint32 unit checksums
    plan: CohortRoundPlan


def encode_round_rows(
    plans: list[CohortRoundPlan],
    side: str,
    interpret: bool | None,
    launches: dict | None = None,
    tracer=None,
) -> dict[int, _SessionRows]:
    """Dispatch every cohort's single-side executor, then collect per-session
    row slices (async dispatch overlaps cohorts).  Shared by Alice and the
    hub — the hub's ``plans`` span all peers' sessions, so the two launches
    per cohort are fused across peers.

    ``launches`` (if given) is bumped at the dispatch site — one
    ``encode_side`` call is one bin-kernel launch plus one sketch matmul —
    so the hub's fusion stats measure dispatches, not planner bookkeeping.
    """
    inflight = []
    for plan in plans:
        store = plan.store
        ss = store.sides[side]
        out = encode_side(
            ss.flat, ss.start, ss.cnt,
            *(jnp.asarray(plan.arrays[k]) for k in _ROUND_ARRAY_KEYS),
            n=store.n,
            t=store.t,
            width=plan.width_a if side == "a" else plan.width_b,
            interpret=interpret,
        )
        if launches is not None:
            launches["kernel_launches"] = launches.get("kernel_launches", 0) + 2
        inflight.append((plan, out))
    per: dict[int, _SessionRows] = {}
    for plan, out in inflight:
        sk, xors, csum = (np.asarray(x) for x in readback(out, "encode", tracer))
        for sess, base, active, bin_seed in plan.members:
            rows = slice(base, base + len(active))
            per[sess.sid] = _SessionRows(
                sess, active, bin_seed, sk[rows], xors[rows], csum[rows], plan
            )
    return per


def encode_round_rows_ext(
    plans: list[CohortRoundPlan],
    side: str,
    level: int,
    interpret: bool | None,
    launches: dict | None = None,
    tracer=None,
) -> dict[int, tuple]:
    """Dispatch every cohort's *incremental* single-side executor for one
    rateless ladder level (DESIGN.md §16) and collect per-session slices.

    Per cohort the syndrome matmul covers only columns
    [t_{level-1}·m, t_level·m) of the (n, t_level) code — the
    ``MSG_PARITY`` payload.  Cohorts whose t-ladder cannot grow at this
    level (the (n-1)//2 code cap) are skipped.  Shared by Alice and the
    hub, which passes plans spanning all peers so the two launches per
    cohort stay fused across peers.

    Returns sid -> (inc (U, t1-t0) int array, t0, t1).
    """
    inflight = []
    for plan in plans:
        store = plan.store
        n, t = store.n, store.t
        t0 = parity_extension_t(t, level - 1, n)
        t1 = parity_extension_t(t, level, n)
        if t1 <= t0:
            continue
        ss = store.sides[side]
        out = encode_side_ext(
            ss.flat, ss.start, ss.cnt,
            *(jnp.asarray(plan.arrays[k]) for k in _ROUND_ARRAY_KEYS),
            n=n, t0=t0, t1=t1,
            width=plan.width_a if side == "a" else plan.width_b,
            interpret=interpret,
        )
        if launches is not None:
            launches["kernel_launches"] = launches.get("kernel_launches", 0) + 2
        inflight.append((plan, t0, t1, out))
    per: dict[int, tuple] = {}
    for plan, t0, t1, out in inflight:
        inc = np.asarray(readback(out, "encode_ext", tracer))
        for sess, base, active, _ in plan.members:
            per[sess.sid] = (inc[base : base + len(active)], t0, t1)
    return per


def round_schema(per: dict[int, _SessionRows], live: list[int]):
    """The frame schema for the given sids, in the given order: both wire
    sides derive it from the same deterministic round state, so frames ship
    no redundant structure (DESIGN.md §9)."""
    return [
        (len(per[sid].active), per[sid].plan.store.t, per[sid].plan.store.m)
        for sid in live
    ]


def serve_phase0(payload: bytes, set_b, cfg: PBSConfig,
                 limit_frac: float | None = ESTIMATE_LIMIT_FRAC):
    """Answer one peer's phase-0 ToW sketch frame (the serving side).

    Returns (d_hat reply frame, the pinned ProtocolPlan, estimator ledger
    bytes covering both framed messages).  Raises ``EstimateOutOfRange``
    when the planned d̂ leaves the PBS operating regime for the pair's
    size (``limit_frac=None`` disables — the legacy burn-the-budget
    behavior); the tree front end (§15) is the route for such pairs.
    """
    set_size_a, sk_a = wf.decode_tow_sketch(payload)
    if len(sk_a) != cfg.ell:
        raise WireError(
            f"peer sent {len(sk_a)} ToW sketches, cfg.ell={cfg.ell}"
        )
    sk_b = tow_sketches(set_b, derive_seed(cfg.seed, 0x70), cfg.ell)
    num = estimate_numerator(sk_a, sk_b)
    reply = wf.encode_dhat(num)
    est_bytes = _framed_len(payload) + len(reply)
    plan = plan_from_estimate(cfg, num, set_size_a)
    check_estimate(
        planned_d(plan.d_est, cfg.gamma), set_size_a + len(set_b), limit_frac
    )
    return reply, plan, est_bytes


def tree_walk_state(elems, cfg: PBSConfig, tcfg: TreeConfig) -> dict:
    """Fresh serving-side tree-walk state (§15): the staged set plus the
    root frontier, the level clock, and the leaf accumulator."""
    return {
        "elems": elems, "cfg": cfg, "tcfg": tcfg,
        "frontier": [(0, SPAN)], "level": 0, "leaves": [], "bytes": 0,
    }


def serve_tree_frame(payload: bytes, walk: dict, stream, tally: dict,
                     tracer, interpret: bool | None) -> bool:
    """Serve one inbound ``MSG_TREE`` digest frame (the serving side's half
    of one tree-walk level, §15); returns True when the walk completed.

    Digest our own frontier — one batched ``tree_digest`` launch — compute
    the verdicts (the serving side holds both digest sets), ship them back,
    and advance the frontier by the shared deterministic split rule.
    Accumulates ``TREE_LEAF`` ranges into ``walk["leaves"]`` and the framed
    exchange bytes into both ``tally["tree"]`` and ``walk["bytes"]``.
    """
    elems, tcfg, frontier = walk["elems"], walk["tcfg"], walk["frontier"]
    level, ell, cnt_a, cs_a, sk_a = wf.decode_tree_digest(payload)
    if level != walk["level"]:
        raise WireError(
            f"tree digest for level {level} at level {walk['level']}"
        )
    if ell != tcfg.ell:
        raise WireError(f"tree digest ell {ell}, configured {tcfg.ell}")
    if len(cnt_a) != len(frontier):
        raise WireError(
            f"tree digest covers {len(cnt_a)} ranges, "
            f"frontier has {len(frontier)}"
        )
    tally["tree"] += _framed_len(payload)
    walk["bytes"] += _framed_len(payload)
    with tracer.span("tree.level.dispatch",
                     level=level, ranges=len(frontier)):
        cnt_b, cs_b, sk_b = level_digests(
            elems, frontier, tcfg, interpret=interpret
        )
    with tracer.span("tree.level.collect", cat="wire",
                     level=level, ranges=len(frontier)):
        verdicts, leaf_ds = level_verdicts(
            level, cnt_a, cs_a, sk_a, cnt_b, cs_b, sk_b, tcfg
        )
        reply = wf.encode_tree_verdict(level, verdicts, leaf_ds)
        stream.send(reply)
        tally["tree"] += len(reply)
        walk["bytes"] += len(reply)
        walk["leaves"] += _leaf_ranges(frontier, verdicts, leaf_ds)
        walk["frontier"] = split_ranges(frontier, verdicts)
        walk["level"] = level + 1
    return not walk["frontier"]


def _leaf_ranges(frontier, verdicts, leaf_ds) -> list[TreeLeaf]:
    """One tree level's ``TREE_LEAF`` ranges, each with its planned d."""
    spans = [r for r, v in zip(frontier, verdicts) if v == wf.TREE_LEAF]
    return [
        TreeLeaf(lo=lo, hi=hi, d_plan=int(d))
        for (lo, hi), d in zip(spans, leaf_ds)
    ]


def serve_epoch_frame(payload: bytes, expected_epoch: int, pending: dict,
                      plans: dict, cfg_of, stream, tally: dict,
                      limit_frac: float | None = ESTIMATE_LIMIT_FRAC) -> bool:
    """Serve one inbound ``MSG_EPOCH`` frame (the serving side's half of
    the epoch handshake, DESIGN.md §11); returns True when the peer owes
    no more epoch frames.

    ``pending`` maps sid -> (staged set, d convention) for the staged
    epoch; estimator sids (convention None) are served in sorted order —
    the same positional contract as ``submit`` — each wrapped ToW sketch
    answered with a wrapped d̂ reply through the shared ``serve_phase0``,
    recording the plan in ``plans``.  A bare epoch-open is only legal
    when nothing re-estimates, and is answered bare.  Ledger mirrors
    ``MSG_MUX``: inner phase-0 bits to the estimator tally, envelope
    bytes to the epoch tally.  The tallies and the plan are recorded before
    the send: a reply whose send raises may still have arrived.
    """
    e, ity, ipayload = wf.decode_epoch(payload)
    if e != expected_epoch:
        raise WireError(f"epoch frame for epoch {e}, expected {expected_epoch}")
    est = [
        sid for sid in sorted(pending)
        if pending[sid][1] is None and sid not in plans
    ]
    if ity is None:
        if est:
            raise WireError("bare epoch-open with estimator sessions pending")
        reply = wf.encode_epoch(e)
        tally["epoch"] += _framed_len(payload) + len(reply)
        stream.send(reply)
        return True
    if ity != wf.MSG_TOW_SKETCH:
        raise WireError(f"unexpected epoch inner frame type 0x{ity:02x}")
    if not est:
        raise WireError("epoch ToW frame with no estimator session pending")
    sid = est[0]
    elems, _ = pending[sid]
    inner_reply, plan, est_bytes = serve_phase0(
        ipayload, elems, cfg_of(sid), limit_frac
    )
    reply = wf.encode_epoch(e, inner_reply)
    tally["estimator"] += est_bytes
    tally["epoch"] += (
        _framed_len(payload) - framed_len(len(ipayload))
        + len(reply) - len(inner_reply)
    )
    plans[sid] = plan
    stream.send(reply)
    return len(est) == 1


def decode_side_b_round(
    plans,
    per: dict[int, _SessionRows],
    sk_a_of: dict,
    launches: dict | None = None,
    tracer=None,
):
    """The serving side's round completion: place each session's
    frame-decoded sketches at its cohort rows, XOR with the resident side,
    run ONE ``bch_decode_batched`` launch per cohort, and build every
    session's reply entry.

    ``sk_a_of`` maps sid -> (U, t) frame sketches; sessions absent from it
    (an evicted hub peer) keep zero rows — padding decodes trivially-ok and
    they are skipped in the result.  Returns (results: sid -> (ok, units),
    ctx: sid -> (sess, active, ok, bin_seed)) — ``ctx`` is what the
    outcome-frame mirror needs.  ``plans`` span every peer of the hub, so
    the decode launch is fused across peers.
    """
    inflight = []
    for plan in plans:
        u_pad = plan.arrays["row_map"].shape[0]
        sk_a = np.zeros((u_pad, plan.store.t), dtype=np.int32)
        sk_b = np.zeros((u_pad, plan.store.t), dtype=np.int32)
        for sess, base, active, _ in plan.members:
            if sess.sid not in sk_a_of:
                continue
            rows = slice(base, base + len(active))
            sk_a[rows] = sk_a_of[sess.sid]
            sk_b[rows] = per[sess.sid].sk
        out = bch_decode_batched(
            jnp.asarray(sk_a ^ sk_b, dtype=jnp.int32),
            n=plan.store.n, t=plan.store.t,
        )
        if launches is not None:
            launches["decode_launches"] = launches.get("decode_launches", 0) + 1
        inflight.append((plan, out))
    tracer = tracer if tracer is not None else current_tracer()
    results: dict[int, tuple] = {}
    ctx: dict[int, tuple] = {}
    for plan, out in inflight:
        ok_pad, pos_pad, cnt_pad = (
            np.asarray(x) for x in readback(out, "decode", tracer)
        )
        # writable: the rateless ladder merges extension verdicts into the
        # per-session ok views in place (DESIGN.md §16)
        ok_pad = np.array(ok_pad)
        with tracer.span("decode.reply_units", n=plan.store.n) as span:
            n_units = 0
            for sess, base, active, bin_seed in plan.members:
                if sess.sid not in sk_a_of:
                    continue
                rows = slice(base, base + len(active))
                row = per[sess.sid]
                ok = ok_pad[rows]
                pos, cnt = pos_pad[rows], cnt_pad[rows]
                units: list[ReplyUnit | None] = []
                for slot in range(len(active)):
                    if not ok[slot]:
                        units.append(None)
                        continue
                    k = int(cnt[slot])
                    p = pos[slot, :k].astype(np.int64)
                    units.append(
                        ReplyUnit(
                            positions=p,
                            xors=row.xors[slot, p],
                            csum=int(row.csum[slot]),
                        )
                    )
                n_units += len(active)
                results[sess.sid] = (ok, units)
                ctx[sess.sid] = (sess, active, ok, bin_seed)
            span.set(units=n_units)
    return results, ctx


def verify_ack_entries(payload: bytes, sessions):
    """Decode a VERIFY frame and compute the serving side's verdicts:
    the peer claims success AND c(A △ D̂) equals our c(B).  Returns
    (ack frame, flags)."""
    entries = wf.decode_verify(payload, len(sessions))
    flags = [
        bool(success) and csum_eff == checksum(sess.state.b)
        for sess, (success, csum_eff) in zip(sessions, entries)
    ]
    return wf.encode_verify_ack(flags), flags


def stream_wire_stats(
    stream: FrameStream, tally: dict, carry: dict | None = None
) -> dict:
    """Measured wire traffic of one stream: exact framed bytes by category
    plus the transport totals (which additionally see ARQ and mux-envelope
    overhead, if any).  ``retransmits``/``rto_ms`` surface the ARQ layer's
    adaptive-retry state when the transport has one (DESIGN.md §13);
    ``resume_frame_bytes`` is the resumption tally — handshake, replayed
    frames, and any aborted partial round, all transport overhead, never
    Formula-(1) bits.  ``carry`` adds the transport byte totals of streams
    torn down by earlier resumptions so the counters stay cumulative."""
    t = stream.transport
    carry = carry or {}
    return {
        "frames_out": stream.frames_out,
        "frames_in": stream.frames_in,
        "frame_bytes_out": stream.bytes_out,
        "frame_bytes_in": stream.bytes_in,
        "transport_bytes_out": t.bytes_out + carry.get("transport_bytes_out", 0),
        "transport_bytes_in": t.bytes_in + carry.get("transport_bytes_in", 0),
        "mux_bytes_out": stream.mux_bytes_out,
        "mux_bytes_in": stream.mux_bytes_in,
        "estimator_frame_bytes": tally["estimator"],
        "protocol_frame_bytes": tally["protocol"],
        "verify_frame_bytes": tally["verify"],
        "epoch_envelope_bytes": tally.get("epoch", 0),
        "resume_frame_bytes": tally.get("resume", 0),
        "tree_frame_bytes": tally.get("tree", 0),
        "retransmits": getattr(t, "retransmits", 0) + carry.get("retransmits", 0),
        "rto_ms": getattr(t, "rto_ms", None),
    }


def roll_back(tally: dict, marks: dict) -> None:
    """Move a partial attempt's bytes — each category's excess over its
    mark at the last barrier — to the resume tally: the attempt re-runs,
    so the Formula-(1) categories count it once (DESIGN.md §13)."""
    for cat, mark in marks.items():
        tally["resume"] += tally[cat] - mark
        tally[cat] = mark


def reattach(old: FrameStream, transport: Transport,
             carry: dict) -> tuple[FrameStream, dict]:
    """A resumed stream over ``transport``: the old stream's framing and
    frame counters carry over, and the returned ``carry`` adds the old
    transport's totals so ``stream_wire_stats`` stays cumulative."""
    t = old.transport
    carry = {
        "transport_bytes_out": t.bytes_out + carry.get("transport_bytes_out", 0),
        "transport_bytes_in": t.bytes_in + carry.get("transport_bytes_in", 0),
        "retransmits": getattr(t, "retransmits", 0) + carry.get("retransmits", 0),
    }
    stream = FrameStream(transport, channel=old.channel)
    for k in ("frames_out", "frames_in", "bytes_out", "bytes_in",
              "mux_bytes_out", "mux_bytes_in"):
        setattr(stream, k, getattr(old, k))
    return stream, carry


class AliceEndpoint:
    """The initiating endpoint; learns A △ B for every submitted session.

    Her serving peer is a ``HubEndpoint``: with ``channel=`` one of its
    many multiplexed peers, without it the one unwrapped peer of a
    ``BobEndpoint`` (DESIGN.md §9–§10).  ``estimate_limit`` is enforced
    where d̂ is planned, on the serving side; it is accepted here so both
    ends of a pair take the same options.
    """

    side = "a"

    def __init__(
        self,
        transport: Transport,
        *,
        interpret: bool | None = None,
        channel: int | None = None,
        continuous: bool = False,
        degrade: bool = False,
        estimate_limit: float | None = ESTIMATE_LIMIT_FRAC,
        recorder: Recorder | None = None,
        tracer=None,
    ):
        enable_persistent_cache()
        self._stream = FrameStream(transport, channel=channel)
        self._interpret = interpret
        # telemetry (DESIGN.md §14): wire_stats derives from the recorder's
        # wire.* rows; spans/instants go through the tracer (the one given,
        # else the process-wide one; NULL_TRACER = disabled, free)
        self.recorder = recorder if recorder is not None else Recorder()
        self.tracer = tracer if tracer is not None else current_tracer()
        self._continuous = continuous
        self._degrade = degrade
        self._sessions: list[ReconSession | None] = []
        self._est_queue: list[int] = []     # sids awaiting phase 0, in order
        self._pending: dict[int, tuple] = {}   # sid -> (a, cfg) for phase 0
        self._batch: SessionBatch | None = None
        self._tally = {
            "estimator": 0, "protocol": 0, "verify": 0, "epoch": 0,
            "resume": 0, "tree": 0,
        }
        # tree front end (§15): staged (elems, cfg, tcfg) awaiting the walk,
        # and the outcome summary
        self._tree: tuple | None = None
        self.tree_depth = 0
        self.tree_leaves: int | None = None
        self._d_known: dict[int, int | None] = {}
        self._epoch = 0
        self._epoch_pending: dict[int, tuple] | None = None  # sid -> (set, dk)
        self._carry: dict = {}              # totals of resumed-away streams
        self.sessions_degraded = 0          # degradation-ladder escalations
        self.parity_extensions = 0          # rateless ladder levels applied
        self.verified: list[bool] | None = None
        # resumption state (DESIGN.md §13): the last completed local round
        # barrier, the rolling transcript digests at that barrier and the
        # one before, the framed outcome bytes of the last barrier (replayed
        # when the hub missed them), and the per-category tally marks the
        # partial-attempt rollback restores on resume.
        self._rnd = 0
        self._digest = wf.transcript_digest0(0)
        self._digest_prev = self._digest
        self._last_outcome: bytes | None = None
        self._marks = {"protocol": 0, "verify": 0, "epoch": 0, "estimator": 0}
        self.resumes = 0

    # -- submission ------------------------------------------------------

    def submit(self, set_a, cfg: PBSConfig | None = None, d_known: int | None = None) -> int:
        """Enqueue one session (this endpoint holds ``set_a``); the peer
        must ``submit`` the matching ``set_b`` with the same cfg/d_known in
        the same order — session identity is positional, like the paper's
        out-of-band-agreed hash functions."""
        with self.tracer.span("endpoint.submit", side=self.side,
                              keys=len(set_a)):
            cfg = cfg or PBSConfig()
            elems = np.unique(np.asarray(set_a, dtype=np.uint32))
            sid = len(self._sessions)
            self._d_known[sid] = d_known
            if d_known is not None:
                self._sessions.append(self._new_session(
                    sid, elems, plan_from_d_known(cfg, d_known)
                ))
            else:
                self._sessions.append(None)
                self._est_queue.append(sid)
                self._pending[sid] = (elems, cfg)
            return sid

    def _new_session(self, sid, elems, plan) -> ReconSession:
        with self.tracer.span("session.state", side=self.side, keys=len(elems)):
            state = new_session_state(elems, _EMPTY, plan)
        return ReconSession(sid=sid, plan=plan, state=state)

    # -- tree front end (DESIGN.md §15) ----------------------------------

    def submit_tree(self, elems, cfg: PBSConfig | None = None,
                    tree: TreeConfig | None = None) -> None:
        """Stage this endpoint's side of a tree-phase cold start: the walk
        runs before phase 0, and every divergent leaf range becomes an
        ordinary known-d session appended after all regular submits — so
        the peer must ``submit_tree`` its matching side with the same
        ``cfg``/``tree`` (positional contract, like ``submit``)."""
        if self._tree is not None:
            raise RuntimeError("a tree phase is already staged")
        if self._batch is not None:
            raise RuntimeError("tree staging after the session batch formed")
        self._tree = (
            np.unique(np.asarray(elems, dtype=np.uint32)),
            cfg or PBSConfig(),
            tree or TreeConfig(),
        )

    # -- round machinery -------------------------------------------------

    def _ensure_batch(self) -> SessionBatch:
        if self._tree is not None:
            raise WireError("round traffic before the tree phase completed")
        if self._est_queue:
            raise WireError("round traffic before phase 0 completed")
        if self._batch is None:
            self._batch = SessionBatch(
                self._sessions, sides=(self.side,),
                mutable=self._continuous, tracer=self.tracer,
            )
        return self._batch

    # -- continuous sync (DESIGN.md §11) ---------------------------------

    def advance_epoch(self, mutations: dict | None = None, *,
                      d_known: dict | None = None,
                      fold_diff: bool = True) -> int:
        """Stage the next epoch's sets: with ``fold_diff`` (the default)
        each session first folds its learned diff into A — replica
        convergence: A ← A △ D̂ = B — then this side's local churn from
        ``mutations`` (sid -> (added, removed)) applies.  ``d_known``
        (sid -> int | None) *rebinds* a session's d convention from this
        epoch on — an int pins d for this and later epochs, ``None``
        returns the session to re-running the d̂ handshake over the wire;
        sessions not mentioned keep their current convention (initially
        the submit-time one).  The epoch itself runs on the next
        ``run_epoch``, which patches the resident stores with the net
        delta in place.  Requires ``continuous=True`` (stores packed with
        mutation lanes).
        """
        if not self._continuous:
            raise RuntimeError("advance_epoch needs continuous=True")
        if self._est_queue or any(s is None for s in self._sessions):
            raise RuntimeError("advance_epoch before the admission epoch ran")
        if self._epoch_pending is not None:
            raise RuntimeError(f"epoch {self._epoch} is already staged")
        muts = mutations or {}
        unknown = (set(muts) | set(d_known or {})) - set(range(len(self._sessions)))
        if unknown:
            # a typo'd sid must not silently drop the caller's churn
            raise KeyError(f"unknown sid(s) {sorted(unknown)} in epoch advance")
        if d_known:
            self._d_known.update(d_known)
        self._epoch += 1
        pending: dict[int, tuple] = {}
        for s in self._sessions:
            added, removed = muts.get(s.sid, (_EMPTY, _EMPTY))
            st = s.state
            base = effective_set(st.a, st.diff) if fold_diff else st.a
            pending[s.sid] = (
                apply_churn(base, added, removed), self._d_known[s.sid],
            )
        self._epoch_pending = pending
        return self._epoch

    def _expect(self, msg_type: int) -> bytes:
        got, payload = self._stream.recv()
        if got != msg_type:
            raise WireError(f"expected message 0x{msg_type:02x}, got 0x{got:02x}")
        return payload

    @property
    def sessions(self) -> list[ReconSession]:
        return self._sessions

    def _degrade_after(self, rnd: int) -> None:
        """Post-barrier degradation hook: escalate any session whose round
        budget just ran out (both endpoints call this at the same round
        with mirrored state, so their escalations agree; DESIGN.md §13)."""
        if self._degrade:
            escalated = degrade_exhausted(self._ensure_batch(), rnd)
            if escalated:
                self.sessions_degraded += len(escalated)
                self.tracer.instant("endpoint.degrade", round=rnd,
                                    sessions=len(escalated))

    @property
    def wire_stats(self) -> dict:
        """Measured wire traffic: exact framed bytes by category plus the
        transport totals (which additionally see ARQ overhead, if any).

        A derived snapshot of the ``wire.*`` metrics in the recorder —
        same keys and values as the pre-obs ad-hoc dict (DESIGN.md §14).
        """
        self.recorder.publish(
            "wire", stream_wire_stats(self._stream, self._tally, self._carry)
        )
        self.recorder.set("endpoint.resumes", self.resumes)
        self.recorder.set("endpoint.sessions_degraded", self.sessions_degraded)
        self.recorder.set("endpoint.parity_extensions", self.parity_extensions)
        return self.recorder.view("wire")

    def run_epoch(self) -> dict[int, ReconcileResult]:
        """Drive one staged epoch over the wire: the ``MSG_EPOCH``
        handshake (epoch id + d̂ re-estimation through the phase-0 codecs),
        an in-place delta patch of the resident stores, then the same
        round/verify machinery as ``run`` — per-epoch results are
        byte-identical to a fresh session over the epoch's sets.  The
        epoch stays staged until its handshake completes, so a resume
        after a failure mid-handshake runs it again (``resume_run``)."""
        if self._epoch_pending is None:
            raise RuntimeError("no epoch staged: call advance_epoch first")
        pending = self._epoch_pending
        self._marks = {k: self._tally[k] for k in self._marks}
        e = self._epoch
        self.tracer.instant("epoch.open", epoch=e)
        batch = self._ensure_batch()

        est_sids = [sid for sid in sorted(pending) if pending[sid][1] is None]
        sent = {}
        if est_sids:
            for sid in est_sids:
                elems, _ = pending[sid]
                cfg = self._sessions[sid].plan.cfg
                sk = tow_sketches(elems, derive_seed(cfg.seed, 0x70), cfg.ell)
                inner = wf.encode_tow_sketch(sk, len(elems))
                f = wf.encode_epoch(e, inner)
                self._stream.send(f)
                self._tally["epoch"] += len(f) - len(inner)
                sent[sid] = len(inner)
        else:
            f = wf.encode_epoch(e)
            self._stream.send(f)
            self._tally["epoch"] += len(f)

        plans = {}
        for sid in est_sids:
            payload = self._expect(wf.MSG_EPOCH)
            got_e, ity, ipayload = wf.decode_epoch(payload)
            if got_e != e:
                raise WireError(f"epoch frame for epoch {got_e} during epoch {e}")
            if ity != wf.MSG_DHAT:
                raise WireError(
                    f"expected d_hat inside the epoch reply, got {ity}"
                )
            inner_len = framed_len(len(ipayload))
            self._tally["epoch"] += _framed_len(payload) - inner_len
            est_frames = sent[sid] + inner_len
            self._tally["estimator"] += est_frames
            elems, _ = pending[sid]
            plan = plan_from_estimate(
                self._sessions[sid].plan.cfg, wf.decode_dhat(ipayload), len(elems)
            )
            if plan.est_bytes != est_frames:
                raise WireError(
                    f"sid {sid}: epoch estimator frames measure {est_frames} B, "
                    f"accounted {plan.est_bytes} B"
                )
            plans[sid] = plan
        if not est_sids:
            payload = self._expect(wf.MSG_EPOCH)
            got_e, ity, _ = wf.decode_epoch(payload)
            if got_e != e or ity is not None:
                raise WireError(f"bad epoch-open ack for epoch {e}")
            self._tally["epoch"] += _framed_len(payload)

        for sid in sorted(pending):
            elems, dk = pending[sid]
            sess = self._sessions[sid]
            plan = plans.get(sid) or plan_from_d_known(sess.plan.cfg, dk)
            advance_session(batch, sess, plan, new_a=elems, rnd0=0)
        self._epoch_pending = None
        self._reset_rounds()
        return self._run_rounds()

    def run(self) -> dict[int, ReconcileResult]:
        """Drive every session to completion over the wire; sid -> result."""
        if self._epoch_pending is not None:
            raise RuntimeError(
                f"epoch {self._epoch} is staged: call run_epoch, not run"
            )
        self._tree_phase()
        self._phase0()
        self._ensure_batch()
        self._reset_rounds()
        return self._run_rounds()

    def _tree_phase(self) -> None:
        """Drive the staged tree walk (§15): one digest->verdict barrier
        per level — one batched ``tree_digest`` launch a side — then
        install every divergent leaf range as an ordinary known-d session.
        The serving peer mirrors the frontier from the same deterministic
        split rule, so frames never ship range bounds."""
        if self._tree is None:
            return
        elems, cfg, tcfg = self._tree
        self._tree = None
        frontier: list[tuple[int, int]] = [(0, SPAN)]
        leaves: list[TreeLeaf] = []
        level = 0
        while frontier:
            with self.tracer.span("tree.level.dispatch",
                                  level=level, ranges=len(frontier)):
                cnt, cs, sk = level_digests(
                    elems, frontier, tcfg, interpret=self._interpret
                )
                f = wf.encode_tree_digest(level, cnt, cs, sk)
                self._stream.send(f)
                self._tally["tree"] += len(f)
            with self.tracer.span("tree.level.collect", cat="wire",
                                  level=level, ranges=len(frontier)):
                payload = self._expect(wf.MSG_TREE)
                self._tally["tree"] += _framed_len(payload)
                got, verdicts, leaf_ds = wf.decode_tree_verdict(payload)
                if got != level:
                    raise WireError(
                        f"tree verdict for level {got} at level {level}"
                    )
                if len(verdicts) != len(frontier):
                    raise WireError(
                        f"tree verdict covers {len(verdicts)} ranges, "
                        f"frontier has {len(frontier)}"
                    )
                leaves += _leaf_ranges(frontier, verdicts, leaf_ds)
                frontier = split_ranges(frontier, verdicts)
            level += 1
        self.tree_depth = max(level - 1, 0)
        self.tree_leaves = len(leaves)
        for sub, leaf in zip(leaf_slices(elems, leaves), leaves):
            self.submit(sub, cfg, d_known=leaf.d_plan)

    def _reset_rounds(self) -> None:
        """Re-arm the round loop and resumption state for a fresh epoch."""
        self._rnd = 0
        self._digest = wf.transcript_digest0(self._epoch)
        self._digest_prev = self._digest
        self._last_outcome = None
        self._marks = {k: self._tally[k] for k in self._marks}

    def _run_rounds(self) -> dict[int, ReconcileResult]:
        batch = self._ensure_batch()
        tracer = self.tracer
        while True:
            rnd = self._rnd + 1
            plans = batch.plan_round(rnd)
            if not plans:
                break
            with tracer.span("round.encode", round=rnd, cohorts=len(plans)):
                per = encode_round_rows(plans, self.side, self._interpret,
                                        tracer=self.tracer)
            live = sorted(per)
            schema = round_schema(per, live)

            sk_frame = wf.encode_round_sketches(
                rnd, [(per[sid].sk, per[sid].plan.store.m) for sid in live]
            )
            self._stream.send(sk_frame)
            self._tally["protocol"] += len(sk_frame)

            with tracer.span("round.reply_wait", cat="wire", round=rnd,
                             sessions=len(live)):
                payload = self._expect(wf.MSG_ROUND_REPLY)
            self._tally["protocol"] += _framed_len(payload)
            got_rnd, entries = wf.decode_round_reply(payload, schema)
            if got_rnd != rnd:
                raise WireError(f"reply for round {got_rnd} during round {rnd}")

            # the measured main-reply ledger is snapshotted BEFORE the
            # rateless ladder merges extension outcomes into the entries:
            # an ext-recovered unit's positions are measured once, from the
            # extension reply that actually carried them
            measured_of = {}
            ent_of = {}
            for sid, (ok, units) in zip(live, entries):
                row = per[sid]
                u_cnt = len(row.active)
                t_, m_ = row.plan.store.t, row.plan.store.m
                measured_of[sid] = (
                    wf.sketches_ledger_bits(u_cnt, t_, m_)
                    + wf.reply_ledger_bits(ok, units, m_)
                )
                ent_of[sid] = [np.asarray(ok, dtype=bool).copy(), list(units)]
            ext_bits_of, measured_ext = self._rateless_ladder(
                rnd, plans, per, live, ent_of
            )

            done_lists = []
            states = [per[sid].sess.state for sid in live]
            recovered0 = sum(st.recovered for st in states)
            fakes0 = sum(st.fake_rejections for st in states)
            with tracer.span("round.apply_outcomes", round=rnd,
                             sessions=len(live)) as span:
                for sid in live:
                    ok, units = ent_of[sid]
                    row = per[sid]
                    st, plan = row.sess.state, row.sess.plan
                    rloc = rnd - row.sess.rnd0   # local protocol round
                    u_cnt = len(row.active)
                    n, t, m = plan.n, plan.t, plan.m
                    xors_b = np.zeros((u_cnt, n), dtype=np.uint32)
                    csum_b = np.zeros(u_cnt, dtype=np.uint64)
                    positions = []
                    for slot in range(u_cnt):
                        unit = units[slot]
                        if unit is None:
                            positions.append(np.zeros(0, dtype=np.int64))
                            continue
                        positions.append(unit.positions)
                        xors_b[slot, unit.positions] = unit.xors
                        csum_b[slot] = unit.csum
                    reply_bits, done = apply_round_outcomes(
                        st, row.active, ok, positions,
                        row.xors, xors_b, row.csum, csum_b,
                        plan=plan, bin_seed=row.bin_seed, rnd=rloc,
                    )
                    # the measured ledger: sketch bits from what we framed,
                    # reply + parity bits from what the frames actually carried
                    # — must land exactly on the Formula-(1) accounting
                    measured = measured_of[sid] + measured_ext[sid]
                    accounted = u_cnt * (t * m + 1) + reply_bits + ext_bits_of[sid]
                    if measured != accounted:
                        raise WireError(
                            f"sid {sid} round {rnd}: measured {measured} bits != "
                            f"accounted {accounted}"
                        )
                    st.bytes_per_round.append((measured + 7) // 8)
                    st.rounds = rloc
                    done_lists.append(done)
                if tracer.enabled:
                    span.set(
                        recovered=sum(st.recovered for st in states) - recovered0,
                        fake_rejections=sum(st.fake_rejections for st in states)
                        - fakes0,
                        filtered_slots=sum(
                            1 for sid in live
                            for u, good in zip(per[sid].active, ent_of[sid][0])
                            if good and u.filters
                        ),
                    )

            out_frame = wf.encode_round_outcome(rnd, done_lists)
            # commit the barrier BEFORE the send: local state is complete, so
            # a transport failure from here on resumes by replaying this
            # frame instead of re-running the round (DESIGN.md §13)
            self._digest_prev = self._digest
            self._digest = wf.fold_transcript(self._digest, rnd, out_frame)
            self._last_outcome = out_frame
            self._rnd = rnd
            self._tally["protocol"] += len(out_frame)
            self._marks = {k: self._tally[k] for k in self._marks}
            self._stream.send(out_frame)
            tracer.instant("round.barrier", round=rnd, epoch=self._epoch)
            self._degrade_after(rnd)

        with tracer.span("verify", sessions=len(self._sessions)):
            self._verify()
        # lossy-channel tail: keep ACKing the peer's retransmits until quiet
        self._stream.transport.linger()
        results = {
            s.sid: finalize_result(s.state, s.plan) for s in self._sessions
        }
        if tracer.enabled:
            # per-session attribution for trace_report: bytes/diff/rounds
            # against the plan's (n, t, d_est) for the Markov comparison
            for sid, r in results.items():
                p = self._sessions[sid].plan
                tracer.instant(
                    "session.result", sid=sid, rounds=r.rounds,
                    diff=len(r.diff), bytes=r.bytes_sent, success=r.success,
                    n=p.n, t=p.t, g=p.g, d_est=p.d_est,
                    channel=self._stream.channel,
                )
        return results

    def _rateless_ladder(self, rnd, plans, per, live, ent_of):
        """Drive the ``MSG_PARITY`` recovery ladder for one round (§16).

        While any rateless session has units whose BCH decode failed and
        its cohort's t can still grow, ship only the incremental syndrome
        columns for the failing units and fold the serving side's extension replies
        into ``ent_of`` in place — the merged entries drive the single
        ``apply_round_outcomes`` downstream, so settled units are never
        re-sent and split seeds still derive from this round.  Returns
        per-sid (accounted ext bits, measured ext bits); both stay zero on
        the honest path, which therefore remains byte-identical to the
        ``rateless=False`` wire format.
        """
        ext_bits = {sid: 0 for sid in live}
        measured = {sid: 0 for sid in live}
        fail: dict[int, list[int]] = {}
        for sid in live:
            row = per[sid]
            if not row.sess.plan.cfg.rateless:
                continue
            bad = [s for s in range(len(row.active)) if not ent_of[sid][0][s]]
            if bad:
                fail[sid] = bad
        for level in range(1, MAX_PARITY_EXTENSIONS + 1):
            if not fail:
                break
            part_plans = [
                plan for plan in plans
                if any(sess.sid in fail for sess, *_ in plan.members)
            ]
            inc_of = encode_round_rows_ext(
                part_plans, self.side, level, self._interpret,
                tracer=self.tracer,
            )
            parts = [sid for sid in live if sid in fail and sid in inc_of]
            if not parts:
                break  # every failing cohort hit the (n-1)//2 code cap
            blocks = []
            reply_schema = []
            for sid in parts:
                inc, t0, t1 = inc_of[sid]
                m = per[sid].plan.store.m
                blocks.append((inc[fail[sid]], m))
                reply_schema.append((len(fail[sid]), t1, m))
            pf = wf.encode_parity(rnd, level, blocks)
            self._stream.send(pf)
            self._tally["protocol"] += len(pf)
            payload = self._expect(wf.MSG_ROUND_REPLY)
            self._tally["protocol"] += _framed_len(payload)
            got_rnd, ext_entries = wf.decode_round_reply(payload, reply_schema)
            if got_rnd != rnd:
                raise WireError(
                    f"extension reply for round {got_rnd} during round {rnd}"
                )
            for sid, (ok_e, units_e) in zip(parts, ext_entries):
                _, t0, t1 = inc_of[sid]
                m = per[sid].plan.store.m
                slots = fail[sid]
                ext_bits[sid] += len(slots) * ((t1 - t0) * m + 1)
                measured[sid] += wf.parity_ledger_bits(len(slots), t1 - t0, m)
                measured[sid] += wf.reply_ledger_bits(ok_e, units_e, m)
                self.parity_extensions += 1
                self.tracer.instant(
                    "endpoint.parity_extension", sid=sid, round=rnd,
                    level=level, units=len(slots), t=t1,
                )
                ok_m, units_m = ent_of[sid]
                still = []
                for i, slot in enumerate(slots):
                    if ok_e[i]:
                        ok_m[slot] = True
                        units_m[slot] = units_e[i]
                    else:
                        still.append(slot)
                if still:
                    fail[sid] = still
                else:
                    del fail[sid]
        return ext_bits, measured

    def resume(self, transport: Transport) -> None:
        """Reconnect to the hub over a fresh transport after a failure and
        re-align at the last completed round barrier (DESIGN.md §13).

        Rolls any partial-round frame bytes out of the protocol/verify
        tallies into the resume tally (the aborted attempt re-runs, so the
        Formula-(1) ledger must count it exactly once), then runs the
        ``MSG_RESUME`` handshake: we announce our last completed barrier
        and transcript digests; the hub answers with its mirror's barrier.
        Equal barriers must agree on ``digest``; a hub exactly one barrier
        behind (our last outcome frame died in flight) must agree on
        ``digest_prev`` and gets that frame replayed — it applies it
        idempotently from its retained round context.  Anything else means
        divergence or an unresumable peer and raises.  Follow with
        ``resume_run()`` to drive the protocol to completion.
        """
        if self._stream.channel is None:
            raise RuntimeError("resume needs a hub channel-tagged stream")
        if self._last_outcome is None and self._rnd:
            raise RuntimeError("resume before any round barrier completed")
        with self.tracer.span("resume", channel=self._stream.channel,
                              epoch=self._epoch, barrier=self._rnd):
            self._resume(transport)

    def _resume(self, transport: Transport) -> None:
        roll_back(self._tally, self._marks)
        self._stream, self._carry = reattach(self._stream, transport,
                                             self._carry)
        stream = self._stream

        f = wf.encode_resume(
            stream.channel, self._epoch, self._rnd,
            self._digest, self._digest_prev,
        )
        self._stream.send(f)
        payload = self._expect(wf.MSG_RESUME)
        self._tally["resume"] += len(f) + _framed_len(payload)
        ch, epoch, hub_rnd, hub_digest, _ = wf.decode_resume(payload)
        if ch != stream.channel or epoch != self._epoch:
            raise WireError(
                f"resume answer for channel {ch} epoch {epoch}, "
                f"expected channel {stream.channel} epoch {self._epoch}"
            )
        if hub_rnd == self._rnd:
            if hub_digest != self._digest:
                raise WireError("resume transcript diverged at equal barriers")
        elif hub_rnd == self._rnd - 1 and self._last_outcome is not None:
            if hub_digest != self._digest_prev:
                raise WireError("resume transcript diverged one barrier back")
            # the hub missed our last outcome barrier: replay it verbatim
            self._stream.send(self._last_outcome)
            self._tally["resume"] += len(self._last_outcome)
        else:
            raise WireError(
                f"unresumable: hub barrier {hub_rnd}, ours {self._rnd}"
            )
        self.resumes += 1

    def resume_run(self) -> dict[int, ReconcileResult]:
        """Continue a resumed protocol from the re-aligned barrier to
        completion — the round loop picks up at ``self._rnd + 1`` over the
        intact session states and cohort stores, after the epoch handshake
        when a failure interrupted it."""
        if self._epoch_pending is not None:
            return self.run_epoch()
        return self._run_rounds()

    def _phase0(self):
        if not self._est_queue:
            return
        with self.tracer.span("phase0", sessions=len(self._est_queue)):
            self._phase0_exchange()

    def _phase0_exchange(self):
        sent = {}
        for sid in self._est_queue:
            a, cfg = self._pending[sid]
            sk = tow_sketches(a, derive_seed(cfg.seed, 0x70), cfg.ell)
            f = wf.encode_tow_sketch(sk, len(a))
            self._stream.send(f)
            sent[sid] = len(f)
        for sid in list(self._est_queue):
            a, cfg = self._pending.pop(sid)
            payload = self._expect(wf.MSG_DHAT)
            num = wf.decode_dhat(payload)
            est_frames = sent[sid] + _framed_len(payload)
            self._tally["estimator"] += est_frames
            plan = plan_from_estimate(cfg, num, len(a))
            if plan.est_bytes != est_frames:
                raise WireError(
                    f"sid {sid}: estimator frames measure {est_frames} B, "
                    f"accounted {plan.est_bytes} B"
                )
            self._sessions[sid] = self._new_session(sid, a, plan)
        self._est_queue.clear()

    def _verify(self):
        entries = []
        for s in self._sessions:
            success = all(u.done for u in s.state.units)
            entries.append(
                (success, checksum(effective_set(s.state.a, s.state.diff)))
            )
        f = wf.encode_verify(entries)
        self._stream.send(f)
        self._tally["verify"] += len(f)
        payload = self._expect(wf.MSG_VERIFY_ACK)
        self._tally["verify"] += _framed_len(payload)
        self.verified = wf.decode_verify_ack(payload, len(self._sessions))


def _framed_len(payload: bytes) -> int:
    """Exact framed size of a received payload (envelope + type + body)."""
    return framed_len(len(payload))
