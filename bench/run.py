#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` through the served path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); ``loadgen`` generates the cell's
sets from ``--seed`` and drives them through ``repro.net.HubEndpoint`` with
one ``AliceEndpoint`` per peer on its own thread.  Set-up (data, program
import, compile-cache loads, warm-up steps that compile the shapes the
window is likely to use) is timed as ``setup_s``; then whole steps run until
``--seconds`` of them have passed.  The window closes at the end of the
first step that ends after ``--seconds``, so every rate is whole steps over
their whole time (the generator's work between steps is not in it).  Once
it has closed and the chip's peak memory has been read, ``check`` holds
every reconciliation of the window to A xor B; a traced run also replays
the window on the plain reference, in worker processes, for binning's
per-round shapes.

Each metric is read by ``bench/metrics/<name>.py``: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, from a
run that records the program's spans and a profiler trace of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its limit),
and the last lines of standard error are the same comparisons.  Off a TPU,
or with fewer chips than the cell asks for, it prints no result and exits
non-zero.
"""
import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import loadgen  # noqa: E402
import trace_reduce  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end ones untraced, its
    per-layer ones traced (a per-layer metric without ``workloads`` goes
    to every cell that reports the metric it moves)."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names else [])]


def pin_compile_cache() -> None:
    """JAX's persistent compile cache lives in the checkout, whatever the
    environment says, and is never evicted from: eviction races between
    peer threads that compile at once, and a write then fails when another
    thread's entry has no access-time file yet."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_max_size", -1)


def load_program():
    """The system under test, imported from this checkout's ``src``."""
    pin_compile_cache()
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import repro

    where = {Path(p).resolve() for p in repro.__path__}
    if where != {ROOT / "src" / "repro"}:
        raise RuntimeError(f"repro imported from {where}, not {ROOT / 'src'}")
    from repro import net
    from repro.core.pbs import PBSConfig
    from repro.kernels.platform import (
        enable_persistent_cache, retrace_count, retrace_counts)
    from repro.obs import Tracer
    from repro.recon import engine

    enable_persistent_cache()
    return SimpleNamespace(net=net, PBSConfig=PBSConfig, Tracer=Tracer,
                           engine=engine, retrace_count=retrace_count,
                           retrace_counts=retrace_counts)


def require_chips(chips: int) -> None:
    """Exit non-zero off a TPU or short of the chips the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips; JAX found {len(devs)}")


@dataclass
class Run:
    """What the metric readers read."""

    steps: list
    recons: list
    window_s: float
    setup_s: float
    correct_recons: int
    spans: list | None          # (name, seconds) of the hub's spans
    device_trace: dict | None   # trace_reduce.reduce of the window
    work: list | None           # per-round binning shapes of every recon
    peaks: dict | None


def _device_info():
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def measure(workload, program, seconds: float, tracer=None):
    """Whole steps until ``seconds`` of them have passed; with a tracer,
    under the profiler.  Returns the steps, the instant set-up ended, and
    the trace's directory and clock-sync instant (None untraced)."""
    import jax

    logdir = None
    sync = None
    if tracer is not None:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        program.engine.set_dispatch_tracer(tracer)
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
            tracer.instant(trace_reduce.SYNC)
            sync = time.perf_counter()
    setup_end = time.perf_counter()
    steps, elapsed = [], 0.0
    try:
        while elapsed < seconds:
            step = workload.run_step(program, len(steps))
            steps.append(step)
            elapsed += step.t_end - step.t_due
            if any(r.served is None for r in step.recons):
                break   # a failed step: the run is already not correct
    finally:
        if tracer is not None:
            jax.profiler.stop_trace()
            program.engine.set_dispatch_tracer(None)
    return steps, setup_end, (logdir, sync)


def _reduce_trace(tracer, logdir, sync, steps):
    """The window's device trace and hub spans, on the profiler's clock."""
    import shutil

    t_load = time.perf_counter()
    try:
        trace = trace_reduce.load(trace_reduce.find_xplane(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    events = tracer.events()
    sync_ts = next(e["ts"] for e in events if e.get("name") == trace_reduce.SYNC)
    if trace.sync_ns is None:
        raise RuntimeError("the profiler trace has no clock-sync annotation")

    def prof_ns(t):             # host perf_counter seconds -> profiler ns
        return trace.sync_ns + (t - sync) * 1e9

    t0, t1 = prof_ns(steps[0].t_due), prof_ns(steps[-1].t_end)
    main = threading.get_ident()
    spans = []
    for e in events:
        if e.get("ph") != "X" or e.get("tid") != main:
            continue
        s = trace.sync_ns + (e["ts"] - sync_ts) * 1e3
        if t0 <= s and s + e["dur"] * 1e3 <= t1:
            spans.append((e["name"], s, s + e["dur"] * 1e3))
    summary = trace_reduce.reduce(trace, t0, t1, spans)
    log(f"trace: {len(trace.device)} device ops, busy {summary['busy_s']:.3f} s "
        f"of {summary['window_s']:.3f} s, {len(spans)} hub spans; read in "
        f"{time.perf_counter() - t_load:.3f} s")
    return summary, [(name, (e - s) / 1e9) for name, s, e in spans]


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, t_start: float) -> dict:
    """Drive one cell and return its result line (no device check here)."""
    program = load_program()
    tracer = program.Tracer(jax_profiler=True) if trace else None
    t0 = time.perf_counter()
    workload = loadgen.make_workload(config, traffic, seed, tracer)
    t1 = time.perf_counter()
    warm_steps = workload.warm_up(program)
    warm = program.retrace_counts()
    log(f"set-up: program import {t0 - t_start:.3f} s, data {t1 - t0:.3f} s, "
        f"warm-up {time.perf_counter() - t1:.3f} s: steps of "
        f"{[round(s.t_end - s.t_due, 3) for s in warm_steps]} s, retraces "
        f"{[s.retraces for s in warm_steps]}")
    steps, setup_end, (logdir, sync) = measure(workload, program, seconds, tracer)
    setup_s = setup_end - t_start
    recons = [r for s in steps for r in s.recons]
    window_s = sum(s.t_end - s.t_due for s in steps)
    late = {k: v - warm.get(k, 0) for k, v in program.retrace_counts().items()
            if v != warm.get(k, 0)}
    log(f"window: {len(steps)} steps of {[round(s.t_end - s.t_due, 3) for s in steps]} s, "
        f"{len(recons)} reconciliations, {window_s:.3f} s; retraces per step "
        f"{[s.retraces for s in steps]} {late}")
    device = _device_info()

    summary = spans = None
    if trace:
        summary, spans = _reduce_trace(tracer, logdir, sync, steps)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]

    # the program's state is freed before the comparison
    del tracer
    gc.collect()
    t_ref = time.perf_counter()
    counts, failed = check.compare(recons, workload.truth)
    work = None
    if trace:
        refs = check.run_reference(workload.reference_tasks(s.index) for s in steps)
        work = [w for r in recons if r.served is not None
                for w in refs[workload.task_key(r)][1]]
    del workload
    log(f"comparison: {len(recons)} reconciliations in "
        f"{time.perf_counter() - t_ref:.3f} s")
    for r in recons:
        if r.error:
            log(f"step {r.step} peer {r.peer}: {r.error}")

    peaks = None
    if trace and device["platform"] == "tpu":
        peaks = json.loads((BENCH / "peaks.json").read_text())
        if device["kind"] not in peaks:
            raise RuntimeError(f"no peaks for device kind {device['kind']!r}")
        peaks = peaks[device["kind"]]
    run = Run(
        steps=steps, recons=recons, window_s=window_s, setup_s=setup_s,
        correct_recons=len(recons) - failed, spans=spans,
        device_trace=summary, work=work, peaks=peaks,
    )
    metrics = {}
    for m in cell_metrics(spec, cell["name"], trace):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {
        "correct": check.correct(counts),
        "attempted": len(recons),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        out["breakdown"] = {
            "device_ops": [list(kv) for kv in summary["device_ops"]],
            "idle_gaps": [list(kv) for kv in summary["idle_gaps"]],
        }
    out["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                     for k, v in counts.items()}
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = load_spec()
    cells = {c["name"]: c for c in spec["workloads"]}
    if args.workload not in cells:
        sys.exit(f"bench: no cell {args.workload!r}; cells are {sorted(cells)}")
    cell = cells[args.workload]
    config = loadgen.load_json("configs", cell["config"])
    traffic = loadgen.load_json("traffic", cell["traffic"])
    pin_compile_cache()
    require_chips(cell["chips"])
    out = run_cell(spec, cell, config, traffic, args.seed, args.seconds,
                   bool(args.trace), _T_START)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
