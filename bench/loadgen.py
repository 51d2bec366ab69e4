"""The one traffic generator and the closed loop that drives a cell.

A cell is a configuration (``configs/<name>.json``: the deployment's sizes)
under a traffic mix (``traffic/<name>.json``: how its sets diverge and how
they are driven).  Everything here is generated from the run's seed with
numpy and handed to the program as plain uint32 sets; the program never
sees the seed.

One mode of driving, a closed loop through the served path
(``HubEndpoint.serve`` with one ``AliceEndpoint`` per peer on its own
thread):

* ``oneshot`` -- every step is one rejoin: a fresh hub and fresh peers put
  one pair of sets per peer through ``run_hub``, store builds included.
  Every step's pairs are new.  Each is a seeded base pair with every key
  XOR-ed with a mask drawn for that step and peer, and every session has a
  seed of its own, so no step repeats another's keys, hashed rows or
  plans (beyond chance); only the sizes and d repeat.

A reconciliation is one peer's session through one step.  It is timed from
the moment its step was due to the moment its ``AliceEndpoint`` returned
its result.  A step is due once its sets are made, so the window counts
the program's work and not the generator's.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
# A peer that misses a barrier by this much is evicted and its
# reconciliation counts as failed; no sound run comes near it.
DEADLINE_S = 300.0
# Rejoins run before the window: one compiles every round's executors.
# The program pads each of a round's sizes to a power of two, and new data
# meets new combinations of them, so the window still traces a few new
# signatures a step; three warm-up rejoins in place of one traced about
# 0.9 in place of 1.4 a window step on the chip, at 50 s more set-up and
# with no narrower spread between runs.
WARM_STEPS = 1


def load_json(kind: str, name: str) -> dict:
    """``configs/<name>.json`` or ``traffic/<name>.json`` under the bench."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


# -- seeded sets ---------------------------------------------------------


def random_set(size: int, rng: np.random.Generator) -> np.ndarray:
    """``size`` distinct uniform uint32 keys, 0 excluded, in random order."""
    out = np.zeros(0, dtype=np.uint32)
    while len(out) < size:
        need = int((size - len(out)) * 1.1) + 16
        cand = rng.integers(1, 1 << 32, size=need, dtype=np.uint64).astype(np.uint32)
        out = np.unique(np.concatenate([out, cand]))
    rng.shuffle(out)
    return out[:size]


def make_pair_two_sided(size: int, a_only: int, b_only: int, rng):
    """(A, B), |A| = size, |A \\ B| = a_only, |B \\ A| = b_only."""
    base = random_set(size + b_only, rng)
    a = base[:size]
    b = np.concatenate([a[: size - a_only], base[size:]])
    rng.shuffle(b)
    return a, b


def draw_mask(a: np.ndarray, b: np.ndarray, rng) -> np.uint32:
    """A nonzero 32-bit mask that is no key of A or B, so that XOR-ing
    both sets with it gives distinct nonzero keys again."""
    while True:
        m = np.uint32(rng.integers(1, 1 << 32))
        if not ((a == m).any() or (b == m).any()):
            return m


# -- records --------------------------------------------------------------


@dataclass
class Recon:
    """One peer's session through one step, as the program served it."""

    step: int
    peer: int
    t_due: float
    t_done: float | None = None
    # (success, sorted diff, rounds, bytes per round); None when no result
    served: tuple | None = None
    error: str | None = None

    @property
    def latency_ms(self) -> float:
        if self.served is None or self.t_done is None:
            return float("inf")
        return (self.t_done - self.t_due) * 1e3


@dataclass
class Step:
    """One rejoin: its reconciliations and its exact counts."""

    index: int
    t_due: float
    t_end: float
    recons: list[Recon]
    wire_bytes: int
    hub_h2d_bytes: int
    retraces: int


def served_tuple(res) -> tuple:
    """What a result says: success, its diff, and the rounds and bytes
    it took (the last two are read by metrics, not compared)."""
    return (
        bool(res.success),
        np.array(sorted(res.diff), dtype=np.uint32),
        int(res.rounds),
        tuple(int(x) for x in res.bytes_per_round),
    )


class _TimedPeer:
    """Stands in for an ``AliceEndpoint`` in ``run_hub``: runs the peer's
    call and records the instant its result came back."""

    def __init__(self, call):
        self._call = call
        self.t_done: float | None = None

    def run(self):
        res = self._call()
        self.t_done = time.perf_counter()
        return res


def _collect(step, t_due, outcomes, results, errors, timed, labels):
    recons = []
    for ch, peer in labels.items():
        r = Recon(step=step, peer=peer, t_due=t_due)
        out = outcomes.get(ch)
        if ch in errors:
            r.error = f"peer raised {errors[ch]!r}"
        elif out is None or not out.ok or out.verified != [True]:
            r.error = f"hub outcome {getattr(out, 'error_kind', None)}: " \
                      f"{getattr(out, 'error', None)!r}"
        elif ch not in results or 0 not in results[ch]:
            r.error = "no result returned"
        else:
            r.served = served_tuple(results[ch][0])
            r.t_done = timed[ch].t_done
        recons.append(r)
    return recons


# -- the closed loop -------------------------------------------------------


class OneShot:
    """Every step puts a new set of pairs through a fresh hub."""

    def __init__(self, config: dict, traffic: dict, seed: int, tracer=None):
        self.tracer = tracer
        self.seed = seed
        self.pbs = config["pbs"]
        keys = config["keys_per_side"]
        self.d_known = int(round(traffic["diff_frac"] * keys))
        a_only = self.d_known // 2
        rng = np.random.default_rng(seed)
        self.pairs = [
            make_pair_two_sided(keys, a_only, self.d_known - a_only, rng)
            for _ in range(config["peers"])
        ]
        self._draws: dict[int, list] = {}
        self._truths: list | None = None

    def draws(self, step: int) -> list[tuple]:
        """Step ``step``'s (mask, session seed) for every peer, drawn from
        the run's seed and the step's index alone."""
        if step not in self._draws:
            rng = np.random.default_rng([self.seed, step + WARM_STEPS])
            self._draws[step] = [
                (draw_mask(a, b, rng), int(rng.integers(0, 1 << 32)))
                for a, b in self.pairs
            ]
        return self._draws[step]

    def step_sets(self, step: int) -> list[tuple]:
        """(A, B, PBSConfig keywords) of every peer in step ``step``."""
        return [
            (a ^ mask, b ^ mask, {**self.pbs, "seed": s})
            for (a, b), (mask, s) in zip(self.pairs, self.draws(step))
        ]

    def warm_up(self, program) -> list[Step]:
        return [self.run_step(program, -1 - k) for k in range(WARM_STEPS)]

    def run_step(self, program, step: int) -> Step:
        net, PBSConfig = program.net, program.PBSConfig
        sets = self.step_sets(step)
        mark = program.retrace_count()
        t_due = time.perf_counter()
        hub = net.HubEndpoint(recv_deadline=DEADLINE_S, tracer=self.tracer)
        timed, labels, links = {}, {}, []
        for i, (a, b, kwargs) in enumerate(sets):
            ta, tb = net.InMemoryDuplex.pair()
            links.append((ta, tb))
            cfg = PBSConfig(**kwargs)
            ch = hub.add_peer(tb, label=f"peer{i}")
            hub.submit(ch, b, cfg=cfg, d_known=self.d_known)
            ep = net.AliceEndpoint(ta, channel=ch)
            ep.submit(a, cfg=cfg, d_known=self.d_known)
            timed[ch] = _TimedPeer(ep.run)
            labels[ch] = i
        outcomes, results, errors = net.run_hub(
            hub, timed, join_timeout=DEADLINE_S)
        t_end = time.perf_counter()
        recons = _collect(step, t_due, outcomes, results, errors, timed, labels)
        for ta, tb in links:
            ta.close()
            tb.close()
        wire = sum(o.wire_stats["transport_bytes_out"] + o.wire_stats["transport_bytes_in"]
                   for o in outcomes.values())
        return Step(step, t_due, t_end, recons, wire_bytes=wire,
                    hub_h2d_bytes=int(hub.stats["h2d_bytes"]),
                    retraces=program.retrace_count() - mark)

    def truth(self, recon: Recon) -> np.ndarray:
        """A xor B of the reconciliation's pair, sorted: numpy's
        ``setxor1d`` of the base pair, under the step's mask."""
        if self._truths is None:
            self._truths = [np.setxor1d(a, b) for a, b in self.pairs]
        mask = self.draws(recon.step)[recon.peer][0]
        return np.sort(self._truths[recon.peer] ^ mask)

    def reference_tasks(self, step: int) -> list[tuple]:
        """One task ``((step, peer), A, B, PBSConfig keywords, d)`` per
        reconciliation of step ``step``."""
        return [((step, i), a, b, kwargs, self.d_known)
                for i, (a, b, kwargs) in enumerate(self.step_sets(step))]

    @staticmethod
    def task_key(recon: Recon) -> tuple:
        return (recon.step, recon.peer)


MODES = {"oneshot": OneShot}


def make_workload(config: dict, traffic: dict, seed: int, tracer=None):
    return MODES[traffic["mode"]](config, traffic, seed, tracer)
