"""Membership in Alice's base set A by binary search over the session's
sorted A (``SessionState.a_sorted`` / ``core.pbs.in_sorted``).

The recovered-diff split of ``diff_overlay`` and the checksum gating of
``apply_round_outcomes`` must read exactly as they would against a Python
set of A, whether A arrives sorted (every served path), unsorted with
duplicates (the fallback copy), or empty (the hub's Bob-side states); and
a session built from an unsorted A must reconcile exactly like one built
from ``np.unique(A)``.
"""
import copy

import numpy as np
import pytest

from repro.core.hashing import derive_seed, hash_to_range
from repro.core.pbs import (
    KEY_BITS,
    PBSConfig,
    apply_round_outcomes,
    diff_overlay,
    in_sorted,
    new_session_state,
    plan_from_d_known,
    queue_split,
    reconcile,
)
from repro.core.simdata import make_pair
from repro.obs import Tracer, use_tracer
from repro.recon import ReconcileServer

_MAX = (1 << 32) - 1
_N, _T, _G = 127, 7, 2


def _base_a(kind: str, rng) -> np.ndarray:
    """A drawn from [1000, 2^32 - 1000), so D̂ can fall below its min and
    above its max."""
    vals = np.unique(rng.integers(1000, _MAX - 1000, 600, dtype=np.uint64))
    vals = vals.astype(np.uint32)
    if kind == "sorted":
        return vals
    if kind == "unsorted_dups":
        return rng.permutation(np.concatenate([vals, vals[::7]]))
    return np.zeros(0, dtype=np.uint32)


def _d_hat(a: np.ndarray, rng) -> list[int]:
    """Members, non-members, both ends of the key space, and values below
    min(A) and above max(A)."""
    members = [int(x) for x in rng.choice(a, 40)] if len(a) else []
    others = [int(x) for x in rng.integers(1000, _MAX - 1000, 60, dtype=np.uint64)]
    return members + others + [0, 1, 999, _MAX, _MAX - 1, _MAX - 999]


def _oracle_apply(st, a_set, active, ok, positions, xors_a, xors_b,
                  csum_a, csum_b, *, plan, bin_seed, rnd):
    """Alice's endgame against a Python set of A, as it read before the
    binary search; also returns each slot's checksum delta."""
    n, g, m = plan.n, plan.g, plan.m
    bits = 0
    done = [False] * len(active)
    deltas = [0] * len(active)
    for slot, u in enumerate(active):
        if not ok[slot]:
            queue_split(st, u, rnd, plan.cfg.seed)
            continue
        pos = positions[slot]
        bits += len(pos) * (m + KEY_BITS) + KEY_BITS
        delta_sum = 0
        newly = []
        for p in pos:
            s = int(xors_a[slot, int(p)] ^ xors_b[slot, int(p)])
            if s == 0:
                st.fake_rejections += 1
                continue
            sx = np.array([s], dtype=np.uint32)
            if (
                int(hash_to_range(sx, n, bin_seed)[0]) != int(p)
                or int(hash_to_range(sx, g, plan.seed_groups)[0]) != u.group
                or any(int(hash_to_range(sx, 3, fs)[0]) != fk for fs, fk in u.filters)
            ):
                st.fake_rejections += 1
                continue
            newly.append(s)
            delta_sum += -s if (s in a_set) ^ (s in st.diff) else s
        for s in newly:
            st.diff.symmetric_difference_update((s,))
        deltas[slot] = delta_sum
        if int((int(csum_a[slot]) + delta_sum) % (1 << KEY_BITS)) == int(csum_b[slot]):
            u.done = True
            done[slot] = True
    return bits, done, deltas


def _hand_built_round(st, plan, pool, rng, bin_seed):
    """One round's tables over ``st.active_units()``: each unit recovers
    the pool values of its sub-universe (one per bin), plus one fake that
    fails the bin check and, where 0 lands in the unit, the zero XOR."""
    active = st.active_units()
    pool = np.asarray(pool, dtype=np.uint32)
    bins = hash_to_range(pool, plan.n, bin_seed)
    groups = hash_to_range(pool, plan.g, plan.seed_groups)
    xors_a = rng.integers(0, _MAX, (len(active), plan.n), dtype=np.uint64).astype(np.uint32)
    xors_b = xors_a.copy()
    positions = []
    for slot, u in enumerate(active):
        mine = groups == u.group
        for fs, fk in u.filters:
            mine &= hash_to_range(pool, 3, fs) == fk
        used = {}
        for v, p in zip(pool[mine], bins[mine]):
            used.setdefault(int(p), int(v))
        for p, v in used.items():
            xors_b[slot, p] = xors_a[slot, p] ^ np.uint32(v)
        free = [p for p in range(plan.n) if p not in used]
        fake = free[0]                      # holds a value of another bin
        wrong = pool[(bins != fake) & (pool != 0)][0]
        xors_b[slot, fake] = xors_a[slot, fake] ^ wrong
        positions.append(np.array(sorted(used) + [fake, free[1]], dtype=np.int64))
    ok = np.ones(len(active), dtype=bool)
    ok[-1] = False                          # one BCH overload: a 3-way split
    csum_a = rng.integers(0, 1 << KEY_BITS, len(active), dtype=np.uint64)
    return active, ok, positions, xors_a, xors_b, csum_a


@pytest.mark.parametrize("kind", ["sorted", "unsorted_dups", "empty"])
def test_membership_matches_python_set(kind):
    rng = np.random.default_rng(["sorted", "unsorted_dups", "empty"].index(kind))
    a = _base_a(kind, rng)
    b = a[: len(a) // 2].copy()
    plan = plan_from_d_known(
        PBSConfig(seed=5, n_override=_N, t_override=_T, g_override=_G), 40
    )
    tr = Tracer()
    with use_tracer(tr):
        st = new_session_state(a, b, plan)
    (span,) = [e for e in tr.events() if e.get("name") == "session.member_set"]
    assert span["args"] == {"keys": len(a), "sorted": kind != "unsorted_dups"}
    assert st.a is a                                  # group/order index into it
    if kind != "unsorted_dups":
        assert st.a_sorted is a                       # no copy on the served path
    np.testing.assert_array_equal(st.a_sorted, np.unique(a))
    a_set = {int(x) for x in a}

    # diff_overlay: the np.isin split of D̂
    d_hat = _d_hat(a, rng)
    st.diff = set(d_hat)
    d = np.fromiter(st.diff, dtype=np.uint32, count=len(st.diff))
    removed, added = diff_overlay(st)
    in_a = np.isin(d, a)
    np.testing.assert_array_equal(removed, d[in_a])
    np.testing.assert_array_equal(added, d[~in_a])
    np.testing.assert_array_equal(in_sorted(st.a_sorted, d), in_a)
    assert in_sorted(st.a_sorted, []).shape == (0,)

    # apply_round_outcomes on a hand-built round, against the set oracle;
    # D̂ already holds some of the recovered values, so both halves of the
    # effective-membership test toggle
    st.diff = set(d_hat[::3])
    queue_split(st, st.units[0], 0, plan.cfg.seed)    # filtered units too
    rnd = 1
    bin_seed = derive_seed(plan.cfg.seed, 2, rnd)
    active, ok, positions, xors_a, xors_b, csum_a = _hand_built_round(
        st, plan, d_hat, rng, bin_seed
    )
    ref = copy.deepcopy(st)
    # settle even slots on the oracle's checksum, leave odd ones one off
    probe = copy.deepcopy(st)
    *_, deltas = _oracle_apply(
        probe, a_set, probe.active_units(), ok, positions, xors_a, xors_b,
        csum_a, csum_a, plan=plan, bin_seed=bin_seed, rnd=rnd)
    csum_b = np.array([(int(c) + dl + slot % 2) % (1 << KEY_BITS)
                       for slot, (c, dl) in enumerate(zip(csum_a, deltas))],
                      dtype=np.uint64)

    got = apply_round_outcomes(st, active, ok, positions, xors_a, xors_b,
                               csum_a, csum_b, plan=plan, bin_seed=bin_seed, rnd=rnd)
    *exp, _ = _oracle_apply(ref, a_set, ref.active_units(), ok, positions, xors_a,
                            xors_b, csum_a, csum_b, plan=plan, bin_seed=bin_seed, rnd=rnd)
    assert got == tuple(exp)
    assert True in got[1] and st.diff != set(d_hat[::3])  # the round did work
    assert st.diff == ref.diff
    assert (st.fake_rejections, st.decode_failures, st.next_uid) == (
        ref.fake_rejections, ref.decode_failures, ref.next_uid)
    assert [(u.uid, u.group, u.filters, u.done) for u in st.units] == [
        (u.uid, u.group, u.filters, u.done) for u in ref.units]


def test_unsorted_a_reconciles_like_unique_a():
    """The served engine over a state built from a shuffled A gives the
    same diff, rounds and per-round bytes as one built from np.unique(A)."""
    a, b = make_pair(3000, 60, np.random.default_rng(11))
    assert np.any(a[1:] <= a[:-1])                    # make_pair shuffles A
    cfg = PBSConfig(seed=21, n_override=63, t_override=5)
    server = ReconcileServer()
    sids = [server.submit(a, b, cfg=cfg, d_known=60) for _ in range(2)]
    shuffled = server._sessions[sids[1]]
    shuffled.state = new_session_state(a, shuffled.state.b, shuffled.plan)
    assert shuffled.state.a_sorted is not shuffled.state.a
    results = server.run()
    exp = reconcile(a, b, cfg, d_known=60)
    assert exp.rounds >= 2                            # the overlay is read
    for sid in sids:
        got = results[sid]
        assert got.success and got.diff == exp.diff
        assert got.rounds == exp.rounds
        assert got.bytes_per_round == exp.bytes_per_round
