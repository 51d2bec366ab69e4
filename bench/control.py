#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seed <n> [--steps k]

The system states no precision; its guarantee is exactness.  The control
breaks it the way a later change might be tempted to, to cut the latency
tail: the reference itself, put in the program's place, stops after its
first round (``max_rounds = 1``) and returns what that round found.  It
runs on the cell's own traffic at the cell's own size (``--steps`` steps of
it), with no program and no chip, and prints the compared numbers with
their limits; ``correct`` has to come out false.  The benchmark's own runs
never run it.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import loadgen  # noqa: E402

CONTROL = {"max_rounds": 1}


def control_counts(config: dict, traffic: dict, seed: int, steps: int) -> dict:
    """The compared numbers when the control's results stand in for the
    program's over ``steps`` steps of the cell's traffic."""
    workload = loadgen.make_workload(config, traffic, seed)
    ctl = check.run_reference(
        [(key, a, b, {**kw, **CONTROL}, d)
         for key, a, b, kw, d in workload.reference_tasks(s)]
        for s in range(steps))
    recons = []
    for step, peer in ctl:
        r = loadgen.Recon(step=step, peer=peer, t_due=0.0)
        r.served = ctl[(step, peer)][0]
        recons.append(r)
    counts, _ = check.compare(recons, workload.truth)
    return counts


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--steps", type=int, default=1)
    args = p.parse_args(argv)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {c["name"]: c for c in spec["workloads"]}[args.workload]
    counts = control_counts(loadgen.load_json("configs", cell["config"]),
                            loadgen.load_json("traffic", cell["traffic"]),
                            args.seed, args.steps)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": check.correct(counts),
        "checks": {k: {"value": v, "limit": check.LIMITS[k]}
                   for k, v in counts.items()},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
