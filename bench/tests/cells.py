"""Shared helpers: the benchmark's cells at a size the CPU interpreter holds."""
import time

import loadgen
import run

SPEC = run.load_spec()
CELLS = {c["name"]: c for c in SPEC["workloads"]}


def tiny(name: str):
    """The cell's configuration and traffic with every scale cut down."""
    cell = CELLS[name]
    config = loadgen.load_json("configs", cell["config"])
    traffic = loadgen.load_json("traffic", cell["traffic"])
    config.update(peers=2, keys_per_side=3000)
    return config, traffic


def run_tiny(name: str, trace: bool = False, seed: int = 2**31 + 7) -> dict:
    """One run of the cell at the tiny size, with no look for a chip."""
    config, traffic = tiny(name)
    return run.run_cell(SPEC, CELLS[name], config, traffic, seed, 0.2, trace,
                        time.perf_counter())
