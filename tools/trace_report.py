#!/usr/bin/env python
"""Summarize a repro.obs trace: occupancy, per-peer bytes, round histogram.

Reads either export format (the Chrome trace JSON that ``--trace`` /
``Tracer.export_chrome`` writes, or JSONL from ``export_jsonl``) and prints
three sections:

* **occupancy** — wall-clock split of the traced window into host work,
  device wait (``device.readback`` spans: host time blocked in
  ``device_get`` until the chip's outputs are copied back; the device's
  own busy time needs a profiler trace), and wire waits (``cat="wire"``
  spans: round barriers, reply/outcome collection), per thread.
  Overlapping same-category spans on a thread are unioned, so nested
  spans don't double-count.
* **per-peer traffic** — bytes, reconciled diff and rounds per session,
  grouped by peer/channel, from the ``session.result`` / ``peer.result``
  instants the endpoints emit at their freeze points.
* **round histogram** — observed completion-round distribution of the
  traced sessions against the ``core.markov`` §5.3 prediction
  (``expected_round_fractions``) for each (n, t, d̂, g) parameter class,
  so a trace directly shows whether the live system tracks the paper's
  Markov model.

Usage: python tools/trace_report.py TRACE [--kmax K] [--json]
(``--json`` emits the report as one machine-readable JSON document
instead of the text tables.)
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from collections import defaultdict

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.obs.trace import load_events  # noqa: E402


def _union(intervals: list[tuple[float, float]]) -> float:
    """Total covered length of possibly-overlapping [start, end) intervals."""
    total = 0.0
    end = -1.0
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def occupancy(events: list[dict]) -> dict:
    """Host/device-wait/wire split per thread, from the complete ("X")
    spans; device wait is the union of the ``device.readback`` spans."""
    spans = [e for e in events if e.get("ph") == "X"]
    names = {
        e["tid"]: e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    by_tid: dict = defaultdict(lambda: defaultdict(list))
    for e in spans:
        cat = "readback" if e["name"] == "device.readback" else e.get("cat", "host")
        by_tid[e["tid"]][cat].append((e["ts"], e["ts"] + e["dur"]))
    out = {}
    for tid, cats in by_tid.items():
        allspans = [iv for ivs in cats.values() for iv in ivs]
        t0 = min(s for s, _ in allspans)
        t1 = max(e for _, e in allspans)
        wall = t1 - t0
        device_wait = _union(cats.get("readback", []))
        wire = _union(cats.get("wire", []))
        covered = _union(allspans)
        out[names.get(tid, str(tid))] = {
            "wall_ms": wall / 1e3,
            "device_wait_ms": device_wait / 1e3,
            "wire_wait_ms": wire / 1e3,
            "host_ms": (covered - device_wait - wire) / 1e3,
            "device_wait_frac": device_wait / wall if wall else 0.0,
        }
    return out


def per_peer(events: list[dict]) -> dict:
    """bytes / diff / rounds per peer, from session.result + peer.result."""
    peers: dict = defaultdict(
        lambda: {"sessions": 0, "bytes": 0, "diff": 0, "rounds": 0,
                 "failed": 0}
    )
    for e in events:
        if e.get("name") == "session.result":
            a = e["args"]
            key = f"channel{a['channel']}" if "channel" in a else "local"
            p = peers[key]
            p["sessions"] += 1
            p["bytes"] += a["bytes"]
            p["diff"] += a["diff"]
            p["rounds"] += a["rounds"]
            p["failed"] += 0 if a["success"] else 1
        elif e.get("name") == "peer.result":
            a = e["args"]
            p = peers[a.get("peer") or f"channel{a['channel']}"]
            p["resumes"] = a.get("resumes", 0)
            p["protocol_bytes"] = a.get("protocol_bytes", 0)
            p["resume_bytes"] = a.get("resume_bytes", 0)
            if not a.get("ok", True):
                p["failed"] += 1
    for p in peers.values():
        p["bytes_per_diff"] = round(p["bytes"] / max(1, p["diff"]), 2)
    return dict(peers)


def round_histogram(events: list[dict], kmax: int = 4) -> list[dict]:
    """Observed completion-round histogram vs the core.markov prediction,
    one entry per (n, t, d_est, g) parameter class seen in the trace."""
    classes: dict = defaultdict(list)
    for e in events:
        if e.get("name") == "session.result":
            a = e["args"]
            if "g" in a and a.get("success"):
                classes[(a["n"], a["t"], a["d_est"], a["g"])].append(
                    a["rounds"])
    out = []
    for (n, t, d, g), rounds in sorted(classes.items()):
        kmax_c = max(kmax, max(rounds))
        hist = [0] * kmax_c
        for r in rounds:
            hist[min(r, kmax_c) - 1] += 1
        entry = {
            "n": n, "t": t, "d_est": d, "g": g,
            "sessions": len(rounds),
            "rounds_hist": hist,
            "mean_rounds": round(sum(rounds) / len(rounds), 3),
        }
        try:
            from repro.core.markov import expected_round_fractions
            fracs = expected_round_fractions(n, t, d, g, kmax=kmax_c)
            entry["markov_round_fracs"] = [round(f, 4) for f in fracs]
            # the model predicts element-resolution fractions per round;
            # a session completes in round k once its last element lands,
            # so the predicted mean completion round is bounded below by
            # sum(k * frac_k) — report both for side-by-side reading
            entry["markov_mean_round"] = round(
                sum((k + 1) * f for k, f in enumerate(fracs)), 3
            )
        except Exception as exc:  # model out of range for these params
            entry["markov_error"] = str(exc)
        out.append(entry)
    return out


def build_report(events: list[dict], kmax: int = 4) -> dict:
    counts: dict = defaultdict(int)
    for e in events:
        counts[e.get("name", "?")] += 1
    return {
        "events": len(events),
        "occupancy": occupancy(events),
        "peers": per_peer(events),
        "round_histogram": round_histogram(events, kmax=kmax),
        "event_counts": dict(sorted(counts.items())),
    }


def print_report(rep: dict) -> None:
    print(f"trace: {rep['events']} events")
    print("\n== occupancy (per thread) ==")
    for name, o in rep["occupancy"].items():
        print(
            f"  {name:>24}: wall {o['wall_ms']:9.2f} ms | "
            f"host {o['host_ms']:9.2f} | device wait "
            f"{o['device_wait_ms']:9.2f} ({o['device_wait_frac']:5.1%}) | "
            f"wire wait {o['wire_wait_ms']:9.2f}"
        )
    if rep["peers"]:
        print("\n== per-peer traffic ==")
        for name, p in sorted(rep["peers"].items()):
            extra = ""
            if "resumes" in p:
                extra = (f" resumes={p['resumes']}"
                         f" resume_bytes={p.get('resume_bytes', 0)}")
            print(
                f"  {name:>12}: sessions={p['sessions']} bytes={p['bytes']} "
                f"diff={p['diff']} rounds={p['rounds']} "
                f"bytes/diff={p['bytes_per_diff']} failed={p['failed']}"
                + extra
            )
    if rep["round_histogram"]:
        print("\n== round histogram vs core.markov ==")
        for h in rep["round_histogram"]:
            print(
                f"  n={h['n']} t={h['t']} d_est={h['d_est']} g={h['g']} "
                f"({h['sessions']} sessions)"
            )
            print(f"    observed rounds hist: {h['rounds_hist']} "
                  f"(mean {h['mean_rounds']})")
            if "markov_round_fracs" in h:
                print(f"    markov round fracs:   {h['markov_round_fracs']} "
                      f"(mean {h['markov_mean_round']})")
            else:
                print(f"    markov: {h['markov_error']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace JSON or JSONL export")
    ap.add_argument("--kmax", type=int, default=4,
                    help="rounds to model in the Markov comparison")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text")
    args = ap.parse_args(argv)
    events = load_events(args.trace)
    if not events:
        print("FAIL: trace holds no events", file=sys.stderr)
        return 1
    rep = build_report(events, kmax=args.kmax)
    if args.json:
        json.dump(rep, sys.stdout, indent=1)
        print()
    else:
        print_report(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
