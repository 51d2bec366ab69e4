"""``group_view`` against the comparison-sort view it replaces.

``group_view`` orders a key array by group with a stable sort over the
narrowest id dtype that holds ``g`` (uint8 up to 256 groups, uint16 up to
65,536, int64 above) and counts the group bounds.  The contract is the
view a stable int64 argsort plus ``searchsorted`` gives: the same ``grp``,
``order`` and ``bounds``, in value and dtype, and so the same per-group
segments in every store built from it.
"""
from dataclasses import replace

import numpy as np
import pytest

from repro.core.hashing import hash_to_range
from repro.core.pbs import (
    PBSConfig,
    group_view,
    new_session_state,
    plan_from_d_known,
)
from repro.core.simdata import make_pair
from repro.obs import Tracer, use_tracer
from repro.recon.session import ReconSession, SessionBatch

SEED_GROUPS = 0x5EED


def reference_group_view(elems: np.ndarray, g: int, seed_groups: int):
    grp = hash_to_range(elems, g, seed_groups)
    order = np.argsort(grp, kind="stable")
    bounds = np.searchsorted(grp[order], np.arange(g + 1))
    return grp, order, bounds


def _keys(kind: str) -> np.ndarray:
    rng = np.random.default_rng(18)
    if kind == "empty":
        return np.zeros(0, dtype=np.uint32)
    if kind == "one":
        return np.array([0xDEADBEEF], dtype=np.uint32)
    if kind == "sorted_unique":          # as every served caller hands it over
        return np.unique(rng.integers(0, 2**32, 5_000, dtype=np.uint32))
    if kind == "unsorted_dups":
        base = rng.integers(0, 2**32, 1_000, dtype=np.uint32)
        return rng.permutation(np.concatenate([base, base[:300], base[:50]]))
    assert kind == "random_1e5"
    return rng.integers(0, 2**32, 100_000, dtype=np.uint32)


@pytest.mark.parametrize(
    "kind", ["empty", "one", "sorted_unique", "unsorted_dups", "random_1e5"]
)
@pytest.mark.parametrize("g", [1, 200, 255, 256, 257, 2_000, 65_536, 65_537])
def test_group_view_matches_comparison_sort(g, kind):
    elems = _keys(kind)
    got = group_view(elems, g, SEED_GROUPS)
    ref = reference_group_view(elems, g, SEED_GROUPS)
    for name, x, y in zip(("grp", "order", "bounds"), got, ref):
        assert x.dtype == y.dtype == np.int64, (name, x.dtype, y.dtype)
        assert np.array_equal(x, y), name
    assert len(got[2]) == g + 1 and got[2][-1] == len(elems)


@pytest.mark.parametrize(
    "g, key_bits",
    [(1, 8), (256, 8), (257, 16), (65_536, 16), (65_537, 64)],
)
def test_group_view_span_names_key_width(g, key_bits):
    cfg = PBSConfig(seed=3, n_override=127, t_override=7, g_override=g)
    plan = plan_from_d_known(cfg, 8)
    a, b = make_pair(500, 8, np.random.default_rng(3))
    tr = Tracer()
    with use_tracer(tr):
        new_session_state(a, b, plan)
    [span] = [e for e in tr.events() if e["name"] == "session.group_view"]
    assert span["args"] == {"keys": len(a) + len(b), "key_bits": key_bits}


def _session(sid: int, g: int, seed: int, *, view=group_view) -> ReconSession:
    cfg = PBSConfig(seed=seed, n_override=127, t_override=7, g_override=g)
    plan = plan_from_d_known(cfg, 8)
    a, b = make_pair(4_000, 8, np.random.default_rng(seed))
    st = new_session_state(a, b, plan)
    grp_a, order_a, bounds_a = view(a, g, plan.seed_groups)
    grp_b, order_b, bounds_b = view(b, g, plan.seed_groups)
    st = replace(st, group_a=grp_a, order_a=order_a, bounds_a=bounds_a,
                 group_b=grp_b, order_b=order_b, bounds_b=bounds_b)
    return ReconSession(sid=sid, plan=plan, state=st)


@pytest.mark.parametrize("g", [200, 2_000])
def test_cohort_store_rows_match_reference_view(g):
    """A store built from two sessions' views holds the same rows, byte for
    byte, as one built from the comparison-sort views."""
    stores = []
    for view in (group_view, reference_group_view):
        sessions = [_session(i, g, 40 + i, view=view) for i in range(2)]
        stores.append(SessionBatch(sessions).store_for(sessions[0].code_key))
    got, ref = stores
    assert got.row_of == ref.row_of
    for side in ("a", "b"):
        x, y = got.sides[side], ref.sides[side]
        assert np.array_equal(np.asarray(x.flat), np.asarray(y.flat)), side
        assert np.array_equal(np.asarray(x.cnt), np.asarray(y.cnt)), side
        assert np.array_equal(x.start_host, y.start_host), side
        keys = sum(len(getattr(s.state, side)) for s in sessions)
        assert len(x.cnt_host) == 2 * g and int(x.cnt_host.sum()) == keys
