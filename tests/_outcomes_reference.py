"""Pre-vectorisation *scalar* ``apply_round_outcomes``, kept verbatim as a
test-only oracle.

This is Alice's per-difference Python loop exactly as it shipped before
``core.pbs.apply_round_outcomes`` became whole-round array operations
(DESIGN.md §2, §5).  ``tests/test_outcomes_vectorized.py`` runs both over
identical rounds and asserts identical bits, done flags, D̂, counters and
unit queue, which is what licenses the array form to claim "same outcomes,
a fraction of the host time".

Do not "optimize" this module: its value is being the old code.
"""
from __future__ import annotations

import numpy as np

from repro.core.hashing import hash_to_range
from repro.core.pbs import (
    KEY_BITS,
    ProtocolPlan,
    SessionState,
    in_sorted,
    queue_split,
)


def apply_round_outcomes(
    st: SessionState,
    active: list,
    ok,
    positions,
    xors_a: np.ndarray,
    xors_b: np.ndarray,
    csum_a: np.ndarray,
    csum_b: np.ndarray,
    *,
    plan: ProtocolPlan,
    bin_seed: int,
    rnd: int,
) -> tuple[int, list[bool]]:
    """Alice's per-unit endgame for one round: recovery via the XOR trick
    (Procedure 1), fake rejection (Procedure 3), checksum gating (§2.2.3),
    and the 3-way-split re-queue on BCH overload (§3.2).

    All arrays are indexed by the unit's position (slot) in ``active``:
    ``positions[slot]`` is the decoded bin index array, ``xors_*[slot]`` the
    (n,) per-bin XOR folds, ``csum_*[slot]`` the unit checksums.  Mutates
    ``st`` (diff, unit queue, counters) and returns (bits, done): the
    Bob->Alice bits this round adds to Formula (1) — the caller accounts
    the Alice->Bob sketches — and the per-slot checksum-settled flags that
    the endpoint path ships to Bob as the round-outcome frame so he can
    mirror the unit queue.
    """
    cfg, n, g, m = plan.cfg, plan.n, plan.g, plan.m
    bits = 0
    done = [False] * len(active)
    for slot, u in enumerate(active):
        if not ok[slot]:
            queue_split(st, u, rnd, cfg.seed)
            continue
        pos = positions[slot]
        # Bob -> Alice: bin indices, his XOR sums, his checksum (Formula 1).
        bits += len(pos) * (m + KEY_BITS) + KEY_BITS
        delta_sum = 0
        newly = []
        for p in pos:
            s = int(xors_a[slot, int(p)] ^ xors_b[slot, int(p)])
            if s == 0:
                st.fake_rejections += 1
                continue
            sx = np.array([s], dtype=np.uint32)
            # Procedure 3: s must belong to this unit's sub-universe.
            if (
                int(hash_to_range(sx, n, bin_seed)[0]) != int(p)
                or int(hash_to_range(sx, g, plan.seed_groups)[0]) != u.group
                or any(int(hash_to_range(sx, 3, fs)[0]) != fk for fs, fk in u.filters)
            ):
                st.fake_rejections += 1
                continue
            newly.append(s)
        for s, in_a in zip(newly, in_sorted(st.a_sorted, newly).tolist()):
            delta_sum += -s if in_a ^ (s in st.diff) else s
        for s in newly:
            st.diff.symmetric_difference_update((s,))
        new_csum = int((int(csum_a[slot]) + delta_sum) % (1 << KEY_BITS))
        if new_csum == int(csum_b[slot]):
            u.done = True
            done[slot] = True
    return bits, done
