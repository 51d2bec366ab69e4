"""Profiler trace -> device busy and idle, per-op device time, idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's
own ``ProfileData``, into plain tuples; ``reduce`` works on those tuples
alone, so its self-test runs on a small recorded trace without a chip.

* Device ops are the top-level events on the ``XLA Ops`` line of every
  ``/device:TPU:<i>`` plane (a loop's body ops lie inside the loop's event
  and are not counted again), named ``<program>/<instruction>`` after the
  ``XLA Modules`` event that holds them.  Asynchronous copies (``Async XLA
  Ops``) are not operations here.  Busy time is the union of the ops'
  intervals inside the window; idle share is one minus busy over the
  window.  Per-op time is the sum of each name's durations.
* Idle gaps are the intervals inside the window in which no device op
  ran.  Each gap is attributed to the innermost host span (from the
  program's ``Tracer``, carried onto the profiler's clock) that covers the
  gap's midpoint on the serving thread, or to ``(no host span)``; the
  spans of one thread nest, so the innermost is the one that started
  last.
"""
from __future__ import annotations

import bisect
import glob
import json
from dataclasses import dataclass

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = "bench.clock_sync"


@dataclass
class Trace:
    """A profiler trace as tuples: ``device`` holds (chip, op, start_ns,
    dur_ns); ``sync_ns`` is the profiler-clock start of the clock-sync
    annotation, or None when the trace has none."""

    device: list
    sync_ns: float | None

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        return cls([tuple(e) for e in doc["device"]], doc["sync_ns"])


def find_xplane(logdir: str) -> str:
    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, found {paths}")
    return paths[0]


def op_label(module: str, op: str) -> str:
    """``<program>/<instruction>`` from the trace's names: the program
    without its fingerprint, the HLO instruction without its text."""
    return f"{module.split('(')[0]}/{op.split(' = ')[0].lstrip('%')}"


def top_level(events):
    """The (name, start, dur) events not nested inside an earlier one: a
    loop's body ops lie inside the loop's own event on the same line."""
    out, end = [], float("-inf")
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        if s >= end:
            out.append((name, s, d))
            end = s + d
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    device, sync_ns = [], None
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = int(plane.name[len(DEVICE_PLANE):])
            lines = {line.name: [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                                 for ev in line.events]
                     for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)}
            modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
            starts = [m[1] for m in modules]
            for name, s, d in top_level(lines.get(OPS_LINE, [])):
                i = bisect.bisect_right(starts, s) - 1
                module = modules[i][0] if i >= 0 and s < starts[i] + modules[i][2] else "?"
                device.append((chip, op_label(module, name), s, d))
        elif sync_ns is None and plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC:
                        sync_ns = float(ev.start_ns)
                        break
    return Trace(device, sync_ns)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(trace: Trace, t0: float, t1: float):
    for chip, name, s, d in trace.device:
        s2, e2 = max(s, t0), min(s + d, t1)
        if e2 > s2:
            yield chip, name, s2, e2


def _innermost(spans, starts, longest, t) -> str:
    """Name of the innermost span covering ``t``, for spans of one thread
    (properly nested): the covering span that started last.  No span that
    started more than ``longest`` before ``t`` can cover it."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and t - starts[i] <= longest:
        name, _, end = spans[i]
        if end >= t:
            return name
        i -= 1
    return "(no host span)"


def reduce(trace: Trace, t0: float, t1: float, host_spans=(), top: int = 10) -> dict:
    """Reduce the window [t0, t1) (profiler-clock ns).

    ``host_spans`` are (name, start_ns, end_ns) on the profiler's clock.
    Returns busy seconds averaged over chips, the window's seconds, device
    time per op name, op counts, and the top idle-gap attribution.
    """
    clipped = list(_clip(trace, t0, t1))
    chips = sorted({c for c, *_ in clipped}) or [0]
    busy_by_chip = {}
    for chip in chips:
        busy_by_chip[chip] = _union((s, e) for c, _, s, e in clipped if c == chip)
    busy_ns = sum(sum(e - s for s, e in u) for u in busy_by_chip.values()) / len(chips)
    op_s: dict[str, float] = {}
    op_n: dict[str, int] = {}
    for _, name, s, e in clipped:
        op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
        op_n[name] = op_n.get(name, 0) + 1

    # idle gaps of the first chip, attributed to the covering host span
    gaps, cur = [], t0
    for s, e in busy_by_chip[chips[0]]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    spans = sorted(host_spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    by_span: dict[str, float] = {}
    for gs, ge in gaps:
        key = _innermost(spans, starts, longest, (gs + ge) / 2)
        by_span[key] = by_span.get(key, 0.0) + (ge - gs) / 1e9

    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "op_s": op_s,
        "op_count": op_n,
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(by_span.items(), key=lambda kv: -kv[1])[:top],
    }


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
