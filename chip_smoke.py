#!/usr/bin/env python3
"""Smoke test of the served PBS path on one TPU chip.

Drives the reconciliation service once through the entry points a user
calls, at replica anti-entropy size (each side holds 10^6 distinct uint32
keys, d = 1000), and holds every session to the plain reference
``repro.core.pbs.reconcile``.  Everything is generated from ``--seed`` and
runs in this one process: peers are threads, never child processes.

1. device  -- JAX's first device is a TPU, the kernels resolve to compiled
              (not interpreted) mode, and the jitted ``encode_side`` at this
              run's shapes lowers to a Mosaic kernel (``tpu_custom_call``);
2. engine  -- ``ReconcileServer`` with 4 sessions: 2 known-d, 1 estimator
              (phase-0 ToW sketches on the chip) and 1 rateless session
              planned at d/10 (so ``execute_round_ext`` runs);
3. hub     -- one ``HubEndpoint`` serving the same 4 pairs from 4
              ``AliceEndpoint`` peers (3 in-memory pipes, 1 TCP loopback
              socket) plus a cold-start peer that joins through the tree
              front end with no d estimate (10^5 keys, 1% divergence);
4. oracle  -- success, diff, rounds and bytes per round of every session
              equal the reference; the tree peer's diff union equals A xor B;
5. warm    -- phases 2 and 3 again on fresh servers and hubs, with zero
              retraces;
6. outage  -- a hub serving 2 known-d peers at ten times the divergence
              (d = 10^4 at the default sizes: the (511, 10) code over
              GF(2^9), 2,000 units a session), equal to the reference.

Times printed are smoke wall time on the host clock, compilation included:
they are not benchmark metrics.  The script fails (exit code != 0, no
verdict) when JAX finds no TPU.  The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage: python chip_smoke.py [--seed 0] [--keys 1000000] [--d 1000]
                            [--tree-keys 100000] [--deadline 600]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "src"))


@dataclass
class Case:
    kind: str
    a: np.ndarray
    b: np.ndarray
    cfg: object
    d_known: int | None


def log(msg: str) -> None:
    print(msg, flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keys", type=int, default=1_000_000,
                   help="keys per side of each phase-2/3 pair")
    p.add_argument("--d", type=int, default=1000,
                   help="symmetric difference of each phase-2/3 pair")
    p.add_argument("--tree-keys", type=int, default=100_000,
                   help="keys per side of the cold-start tree peer (1%% diverge)")
    p.add_argument("--deadline", type=float, default=600.0,
                   help="hub per-peer barrier deadline, seconds")
    return p.parse_args(argv)


def check_device():
    """Phase 1a: a TPU, and the package beside this script."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}")
    import repro

    where = [Path(p).resolve() for p in repro.__path__]
    if where != [HERE / "src" / "repro"]:
        sys.exit(f"chip_smoke: repro imported from {where}, not {HERE / 'src'}")
    return dev, len(devices)


def make_cases(args):
    from repro.core.pbs import PBSConfig
    from repro.core.simdata import make_pair_two_sided
    from repro.tree.partition import TreeConfig

    def pair(i, keys, d):
        rng = np.random.default_rng([args.seed, i])
        return make_pair_two_sided(keys, d // 2, d - d // 2, rng)

    kinds = (
        ("known-d", {}, args.d),
        ("known-d", {}, args.d),
        ("estimator", {}, None),
        ("rateless", {"rateless": True}, max(1, args.d // 10)),
    )
    cases = [
        Case(kind, *pair(i, args.keys, args.d),
             PBSConfig(seed=args.seed * 16 + i, **extra), dk)
        for i, (kind, extra, dk) in enumerate(kinds)
    ]
    ta, tb = pair(len(kinds), args.tree_keys, args.tree_keys // 100)
    tree = Case("tree", ta, tb, PBSConfig(seed=args.seed * 16 + len(kinds)), None)
    return cases, tree, TreeConfig(seed=args.seed)


def check_kernels_compiled(case: Case) -> None:
    """Phase 1b: the interpreter fallback is off, and the served single-side
    executor at this run's first-round shapes lowers to a Mosaic kernel."""
    from repro.core.pbs import new_session_state, plan_from_d_known
    from repro.kernels.platform import resolve_interpret
    from repro.recon import engine
    from repro.recon.session import ReconSession, SessionBatch

    assert resolve_interpret(None) is False, "kernels would run interpreted"
    plan = plan_from_d_known(case.cfg, case.d_known)
    empty = np.zeros(0, dtype=np.uint32)
    sess = ReconSession(sid=0, plan=plan,
                        state=new_session_state(case.a, empty, plan))
    cplan = SessionBatch([sess], sides=("a",)).plan_round(1)[0]
    side = cplan.store.a
    text = engine._jitted_side_executor().lower(
        side.flat, side.start, side.cnt,
        *(cplan.arrays[k] for k in (
            "row_map", "unit_valid", "seeds", "removed", "removed_cnt",
            "added", "added_cnt", "fseeds", "fbins", "fcnt",
        )),
        n=plan.n, t=plan.t, width=cplan.width_a, interpret=None,
    ).as_text()
    assert "tpu_custom_call" in text, "encode_side lowered without a TPU kernel"
    log(f"kernels: compiled (encode_side n={plan.n} t={plan.t} "
        f"units={len(cplan.arrays['row_map'])} width={cplan.width_a} "
        "lowers to tpu_custom_call)")


def run_engine(cases):
    """Phase 2: the in-process batched engine."""
    from repro.recon import ReconcileServer

    srv = ReconcileServer()
    sids = [srv.submit(c.a, c.b, cfg=c.cfg, d_known=c.d_known) for c in cases]
    res = srv.run()
    st = srv.stats
    assert st["parity_extensions"] > 0, "the rateless session never extended"
    return [res[s] for s in sids], st


def run_hub_phase(cases, tree: Case, tcfg, deadline: float):
    """Phase 3: one hub serving 4 wire peers and a cold-start tree peer."""
    from repro.net import (
        AliceEndpoint,
        HubEndpoint,
        InMemoryDuplex,
        run_hub,
        tcp_loopback_pair,
    )

    hub = HubEndpoint(recv_deadline=deadline)
    links = [InMemoryDuplex.pair() for _ in cases[1:]] + [tcp_loopback_pair()]
    alices, chans = {}, []
    for c, (ta, tb) in zip(cases, links):
        ch = hub.add_peer(tb, label=c.kind)
        hub.submit(ch, c.b, cfg=c.cfg, d_known=c.d_known)
        ep = AliceEndpoint(ta, channel=ch)
        ep.submit(c.a, cfg=c.cfg, d_known=c.d_known)
        alices[ch] = ep
        chans.append(ch)
    ta, tb = InMemoryDuplex.pair()
    tree_ch = hub.add_peer(tb, label="cold-start")
    hub.submit_tree(tree_ch, tree.b, cfg=tree.cfg, tree=tcfg)
    ep = AliceEndpoint(ta, channel=tree_ch)
    ep.submit_tree(tree.a, tree.cfg, tcfg)
    alices[tree_ch] = ep

    outcomes, results, errors = run_hub(hub, alices, join_timeout=deadline)
    for ta, tb in links[-1:]:
        ta.close()
        tb.close()
    assert not errors, f"peer endpoints raised: {errors}"
    failed = {ch: (o.error_kind, o.error) for ch, o in outcomes.items() if not o.ok}
    assert not failed, f"hub evicted peers: {failed}"
    st = hub.stats
    assert st["parity_extensions"] > 0, "the rateless peer never extended"
    assert outcomes[tree_ch].tree_leaves, "the cold-start peer admitted no leaf"
    return [results[ch][0] for ch in chans], results[tree_ch], st


_FIELDS = ("success", "diff", "rounds", "bytes_per_round", "bytes_sent")


def check_oracle(label, got, expected) -> None:
    """Phase 4: each served session equals the reference field for field."""
    for kind_res, exp in zip(got, expected):
        for f in _FIELDS:
            if getattr(kind_res, f) != getattr(exp, f):
                raise AssertionError(
                    f"{label}: {f} differs from core.pbs.reconcile "
                    f"({getattr(kind_res, f)!r:.80} vs {getattr(exp, f)!r:.80})"
                )


def run_outage_phase(args, deadline: float):
    """Phase 6: a hub serving 2 known-d peers at ten times the divergence,
    each held to ``core.pbs.reconcile``."""
    from repro.core.pbs import PBSConfig, plan_from_d_known, reconcile
    from repro.core.simdata import make_pair_two_sided
    from repro.net import AliceEndpoint, HubEndpoint, InMemoryDuplex, run_hub

    d = 10 * args.d
    hub = HubEndpoint(recv_deadline=deadline)
    alices, cases = {}, []
    for i in range(2):
        rng = np.random.default_rng([args.seed, 100 + i])
        a, b = make_pair_two_sided(args.keys, d // 2, d - d // 2, rng)
        cfg = PBSConfig(seed=args.seed * 16 + 8 + i)
        ta, tb = InMemoryDuplex.pair()
        ch = hub.add_peer(tb, label=f"outage{i}")
        hub.submit(ch, b, cfg=cfg, d_known=d)
        ep = AliceEndpoint(ta, channel=ch)
        ep.submit(a, cfg=cfg, d_known=d)
        alices[ch] = ep
        cases.append(Case("outage", a, b, cfg, d))
    plan = plan_from_d_known(cases[0].cfg, d)
    t0 = time.perf_counter()
    outcomes, results, errors = run_hub(hub, alices, join_timeout=deadline)
    wall = time.perf_counter() - t0
    assert not errors, f"peer endpoints raised: {errors}"
    failed = {ch: (o.error_kind, o.error) for ch, o in outcomes.items() if not o.ok}
    assert not failed, f"hub evicted peers: {failed}"
    expected = [reconcile(c.a, c.b, c.cfg, d_known=d) for c in cases]
    check_oracle("hub/outage", [results[ch][0] for ch in alices], expected)
    st = hub.stats
    log(f"phase hub outage: smoke wall time {wall:.3f} s, peers=2 d={d} "
        f"n={plan.n} t={plan.t} units={plan.g} per session, "
        f"rounds={st['rounds']} kernel_launches={st['kernel_launches']}")


def check_tree(tree_results, tree: Case) -> None:
    from repro.core.pbs import true_diff

    union = set()
    for r in tree_results.values():
        assert r.success, "a tree leaf session failed"
        union |= r.diff
    assert union == true_diff(tree.a, tree.b), "tree peer diff union is wrong"


def main(argv=None) -> int:
    args = parse_args(argv)
    dev, count = check_device()

    from repro.kernels.platform import enable_persistent_cache

    # before the first compilation: JAX fixes its cache when it first compiles
    cache_dir = enable_persistent_cache()
    cases, tree, tcfg = make_cases(args)
    log(f"data: 4 pairs x {args.keys} keys/side d={args.d} "
        f"({', '.join(c.kind for c in cases)}); tree peer {args.tree_keys} "
        f"keys/side d={args.tree_keys // 100}")

    t0 = time.perf_counter()
    check_kernels_compiled(cases[0])
    log(f"phase device: smoke wall time {time.perf_counter() - t0:.3f} s")

    from repro.core.pbs import reconcile
    from repro.kernels.platform import retrace_counts

    t0 = time.perf_counter()
    expected = [reconcile(c.a, c.b, c.cfg, d_known=c.d_known) for c in cases]
    log(f"phase oracle: smoke wall time {time.perf_counter() - t0:.3f} s "
        f"(core.pbs.reconcile, host numpy, 4 pairs)")

    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        got, st = run_engine(cases)
        wall = time.perf_counter() - t0
        check_oracle(f"engine/{label}", got, expected)
        log(f"phase engine {label}: smoke wall time {wall:.3f} s, "
            f"rounds={st['rounds']} retraces={st['retraces']} "
            f"kernel_launches={st['kernel_launches']} "
            f"parity_extensions={st['parity_extensions']} "
            f"h2d_store_bytes={st['h2d_store_bytes']}")

        t0 = time.perf_counter()
        got, tree_res, hst = run_hub_phase(cases, tree, tcfg, args.deadline)
        wall = time.perf_counter() - t0
        check_oracle(f"hub/{label}", got, expected)
        check_tree(tree_res, tree)
        log(f"phase hub {label}: smoke wall time {wall:.3f} s, "
            f"peers=5 rounds={hst['rounds']} retraces={hst['retraces']} "
            f"kernel_launches={hst['kernel_launches']} "
            f"decode_launches={hst['decode_launches']} "
            f"parity_extensions={hst['parity_extensions']} "
            f"tree_leaves={hst['tree_leaves']} "
            f"h2d_store_bytes={hst['h2d_store_bytes']}")
        if label == "cold":
            seen = retrace_counts()
            for fn in ("tow_sketch", "execute_round", "execute_round_ext",
                       "encode_side", "encode_side_ext", "tree_digest",
                       "bch_decode_batched"):
                assert seen.get(fn), f"{fn} never ran"
        else:
            assert st["retraces"] == 0, f"warm engine retraced {st['retraces']}"
            assert hst["retraces"] == 0, f"warm hub retraced {hst['retraces']}"
    run_outage_phase(args, args.deadline)
    log("phase oracle: every engine and hub session equals core.pbs.reconcile; "
        "tree peer diff union equals A xor B")

    entries = len(os.listdir(cache_dir))
    log(f"compile cache: {cache_dir} ({entries} entries)")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
