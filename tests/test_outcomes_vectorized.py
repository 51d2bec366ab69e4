"""The array form of ``core.pbs.apply_round_outcomes`` against the scalar
per-difference loop it replaced (``tests/_outcomes_reference.py``).

Each case is one or more rounds: real rounds of the numpy oracle
``reconcile`` at d = 10^3 and 10^4 on scaled-down sets, and hand-built
rounds that plant each unit's differences at their bins and then add one
edge at a time.  Both implementations run on copies of the same state and
must agree on the bits, the done flags, D̂, the counters and the unit queue.
Hand-built rounds also pick each unit's checksum so that a chosen set of
units settles, and check the done flags against that choice.
"""
import copy

import numpy as np
import pytest

import repro.core.pbs as pbs
from repro.core.hashing import derive_seed, hash_to_range
from repro.core.pbs import (
    KEY_BITS,
    PBSConfig,
    Unit,
    apply_round_outcomes,
    new_session_state,
    plan_from_d_known,
    queue_split,
)
from repro.core.simdata import make_pair_two_sided

from _outcomes_reference import apply_round_outcomes as scalar_apply_round_outcomes

_MOD = 1 << KEY_BITS
_CFG = PBSConfig(seed=7, n_override=63, t_override=5, g_override=4)
_PER_UNIT = 5


def _snapshot(st):
    return {
        "diff": set(st.diff),
        "fake_rejections": st.fake_rejections,
        "decode_failures": st.decode_failures,
        "next_uid": st.next_uid,
        "units": [(u.uid, u.group, u.filters, u.done) for u in st.units],
    }


def _both(st, active, args, kw):
    """Run the array form and the scalar reference on copies of one state;
    return both (result, state snapshot) pairs."""
    out = []
    for fn in (apply_round_outcomes, scalar_apply_round_outcomes):
        st_c, active_c = copy.deepcopy((st, active))
        got = fn(st_c, active_c, *copy.deepcopy(args), **kw)
        out.append((got, _snapshot(st_c)))
    return out


# ---------------------------------------------------------------------------
# real rounds of the oracle
# ---------------------------------------------------------------------------


def _oracle_rounds(size, d, seed, monkeypatch):
    """Rounds 1-3 of ``reconcile`` on a two-sided pair, captured at the
    ``apply_round_outcomes`` call as (state, active, args, kwargs).  Round 1
    overloads some units, so rounds 2 and 3 hold split units."""
    rounds = []
    real = pbs.apply_round_outcomes

    def capture(st, active, *args, **kw):
        if kw["rnd"] <= 3:
            rounds.append(copy.deepcopy((st, active, args, kw)))
        return real(st, active, *args, **kw)

    monkeypatch.setattr(pbs, "apply_round_outcomes", capture)
    a, b = make_pair_two_sided(size, d // 2, d - d // 2, np.random.default_rng(d))
    res = pbs.reconcile(a, b, PBSConfig(seed=seed), d_known=d)
    assert res.success and len(res.diff) == d and res.decode_failures
    assert [r[3]["rnd"] for r in rounds] == [1, 2, 3]
    return [(st, active, args, kw, None) for st, active, args, kw in rounds]


# ---------------------------------------------------------------------------
# hand-built rounds
# ---------------------------------------------------------------------------


def _state(seed):
    rng = np.random.default_rng(seed)
    a = np.unique(rng.integers(1, _MOD - 1, 3000, dtype=np.uint64)).astype(np.uint32)
    b = np.sort(rng.permutation(a)[:2500])
    plan = plan_from_d_known(_CFG, 40)
    return rng, plan, new_session_state(a, b, plan)


def _pool(st, rng):
    """Values in A and values outside it."""
    inside = rng.choice(st.a_sorted, 300, replace=False)
    outside = rng.integers(1, _MOD - 1, 300, dtype=np.uint64).astype(np.uint32)
    return np.unique(np.concatenate([inside, outside]))


def _belongs(vals, u, plan):
    mask = hash_to_range(vals, plan.g, plan.seed_groups) == u.group
    for fs, fk in u.filters:
        mask &= hash_to_range(vals, 3, fs) == fk
    return mask


class _Round:
    """One hand-built round over the active units: every unit recovers up to
    ``_PER_UNIT`` pool values of its sub-universe, one per bin;
    ``accepted[slot]`` lists the values Procedure 3 must accept."""

    def __init__(self, st, plan, rng, rnd=1):
        self.st, self.plan, self.rnd = st, plan, rnd
        self.bin_seed = derive_seed(plan.cfg.seed, 2, rnd)
        self.active = st.active_units()
        u_cnt, n = len(self.active), plan.n
        self.pool = _pool(st, rng)
        self.xors_a = rng.integers(0, _MOD, (u_cnt, n), dtype=np.uint64).astype(np.uint32)
        self.xors_b = self.xors_a.copy()
        self.ok = np.ones(u_cnt, dtype=bool)
        self.positions, self.accepted = [], []
        bins = hash_to_range(self.pool, n, self.bin_seed)
        for slot, u in enumerate(self.active):
            mine = _belongs(self.pool, u, plan)
            used = {}
            for v, p in zip(self.pool[mine].tolist(), bins[mine].tolist()):
                if len(used) < _PER_UNIT:
                    used.setdefault(p, v)
            for p, v in used.items():
                self.xors_b[slot, p] ^= np.uint32(v)
            self.positions.append(sorted(used))
            self.accepted.append([used[p] for p in sorted(used)])
        self.csum_a = rng.integers(0, _MOD, u_cnt, dtype=np.uint64)

    def free(self, slot):
        return [p for p in range(self.plan.n) if p not in self.positions[slot]]

    def inject(self, slot, p, s):
        """Bin ``p`` of ``slot`` XORs to ``s``; Procedure 3 rejects it."""
        self.xors_b[slot, p] = self.xors_a[slot, p] ^ np.uint32(s)
        self.positions[slot] = sorted(self.positions[slot] + [p])

    def deltas(self):
        """Each slot's checksum delta, value by value in slot order."""
        a, diff, out = set(self.st.a_sorted.tolist()), set(self.st.diff), []
        for slot, vals in enumerate(self.accepted):
            out.append(sum(-v if (v in a) ^ (v in diff) else v for v in vals)
                       if self.ok[slot] else 0)
            for v in vals if self.ok[slot] else ():
                diff ^= {v}
        return out

    def case(self, settle):
        """(state, active, args, kwargs, expected done) with Bob's checksum
        equal to Alice's plus the delta exactly where ``settle`` holds."""
        csum_b = np.array(
            [(int(c) + dl + (0 if s else 1)) % _MOD
             for c, dl, s in zip(self.csum_a, self.deltas(), settle)],
            dtype=np.uint64,
        )
        positions = [np.array(p, dtype=np.int64) for p in self.positions]
        args = (self.ok, positions, self.xors_a, self.xors_b, self.csum_a, csum_b)
        kw = dict(plan=self.plan, bin_seed=self.bin_seed, rnd=self.rnd)
        expect = [bool(s and o) for s, o in zip(settle, self.ok)]
        return [(self.st, self.active, args, kw, expect)]


def _alternate(k):
    return [slot % 2 == 0 for slot in range(k)]


def _zero_xor():
    """A decoded bin whose XORs agree, at the bin 0 hashes to: in the unit
    of 0's group only the zero test rejects it."""
    rng, plan, st = _state(1)
    r = _Round(st, plan, rng)
    zero = np.zeros(1, dtype=np.uint32)
    p0 = int(hash_to_range(zero, plan.n, r.bin_seed)[0])
    (z,) = [slot for slot, u in enumerate(r.active) if _belongs(zero, u, plan)[0]]
    assert p0 in r.free(z)
    for slot in range(len(r.active)):
        r.positions[slot] = sorted(r.positions[slot] + [p0])
    return r.case(_alternate(len(r.active)))


def _wrong_bin():
    rng, plan, st = _state(2)
    r = _Round(st, plan, rng)
    bins = hash_to_range(r.pool, plan.n, r.bin_seed)
    for slot, u in enumerate(r.active):
        p = r.free(slot)[0]
        r.inject(slot, p, r.pool[_belongs(r.pool, u, plan) & (bins != p)][0])
    return r.case(_alternate(len(r.active)))


def _wrong_group():
    rng, plan, st = _state(3)
    r = _Round(st, plan, rng)
    bins = hash_to_range(r.pool, plan.n, r.bin_seed)
    for slot, u in enumerate(r.active):
        free = r.free(slot)
        other = ~_belongs(r.pool, u, plan) & np.isin(bins, free)
        v = r.pool[other][0]
        r.inject(slot, int(hash_to_range(np.array([v]), plan.n, r.bin_seed)[0]), v)
    return r.case(_alternate(len(r.active)))


def _split_state(seed, levels):
    """A state whose unit 0 split ``levels`` times: filtered units at
    depth 1 and, for two levels, at depth 2."""
    rng, plan, st = _state(seed)
    queue_split(st, st.units[0], 1, plan.cfg.seed)
    if levels == 2:
        queue_split(st, st.units[-2], 2, plan.cfg.seed)
    return rng, plan, st


def _split_filters(levels):
    rng, plan, st = _split_state(3 + levels, levels)
    r = _Round(st, plan, rng, rnd=levels + 1)
    assert {len(u.filters) for u in r.active} == set(range(levels + 1))
    # a value of the right group and bin but another branch of the split
    bins = hash_to_range(r.pool, plan.n, r.bin_seed)
    for slot, u in enumerate(r.active):
        if not u.filters:
            continue
        parent = Unit(uid=-1, group=u.group, filters=u.filters[:-1])
        wrong = (_belongs(r.pool, parent, plan) & ~_belongs(r.pool, u, plan)
                 & np.isin(bins, r.free(slot)))
        v = r.pool[wrong][0]
        r.inject(slot, int(hash_to_range(np.array([v]), plan.n, r.bin_seed)[0]), v)
    return r.case(_alternate(len(r.active)))


def _decode_failure():
    rng, plan, st = _split_state(6, 1)
    r = _Round(st, plan, rng, rnd=2)
    r.ok[[0, len(r.active) - 1]] = False     # a plain unit and a filtered one
    return r.case([True] * len(r.active))


def _already_in_dhat():
    rng, plan, st = _state(7)
    r = _Round(st, plan, rng)
    flat = [v for vals in r.accepted for v in vals]
    a = set(st.a_sorted.tolist())
    st.diff = {v for v in flat[::2]} | {1, 2, 3}
    assert any(v in a for v in flat[::2]) and any(v not in a for v in flat[::2])
    return r.case(_alternate(len(r.active))[::-1])


def _checksum_wraps():
    rng, plan, st = _state(8)
    r = _Round(st, plan, rng)
    deltas = r.deltas()
    assert any(dl > 0 for dl in deltas) and any(dl < 0 for dl in deltas)
    r.csum_a = np.array([_MOD - 1 if dl > 0 else 0 for dl in deltas], dtype=np.uint64)
    return r.case([True] * len(r.active))


def _repeated_position():
    rng, plan, st = _state(9)
    r = _Round(st, plan, rng)
    for slot in range(len(r.active)):
        p, v = r.positions[slot][0], r.accepted[slot][0]
        r.positions[slot] = sorted(r.positions[slot] + [p] * (slot + 1))
        r.accepted[slot] += [v] * (slot + 1)      # an even count cancels
    fake = r.free(0)[0]
    r.positions[0] += [fake, fake]                # a repeated zero XOR
    return r.case(_alternate(len(r.active)))


def _no_active_units():
    rng, plan, st = _state(11)
    for u in st.units:
        u.done = True
    r = _Round(st, plan, rng)
    assert r.active == []
    return r.case([])


_HAND = {
    "zero_xor": _zero_xor,
    "wrong_bin": _wrong_bin,
    "wrong_group": _wrong_group,
    "split_filters_one_level": lambda: _split_filters(1),
    "split_filters_two_levels": lambda: _split_filters(2),
    "decode_failure_splits": _decode_failure,
    "already_in_dhat": _already_in_dhat,
    "checksum_wraps": _checksum_wraps,
    "repeated_position": _repeated_position,
    "no_active_units": _no_active_units,
}
_ORACLE = {"oracle_d1000": (20000, 1000, 1), "oracle_d10000": (30000, 10000, 11)}


@pytest.mark.parametrize("case", list(_ORACLE) + list(_HAND))
def test_outcomes_match_scalar_reference(case, monkeypatch):
    if case in _ORACLE:
        rounds = _oracle_rounds(*_ORACLE[case], monkeypatch)
    else:
        rounds = _HAND[case]()
    for st, active, args, kw, expect in rounds:
        (got, state), (want, want_state) = _both(st, active, args, kw)
        assert got == want
        assert state == want_state
        if expect is not None:
            assert got[1] == expect
        if case != "no_active_units":
            assert state != _snapshot(st)             # the round did work
