"""What binning needs, from a round's shapes alone.

One round of one session, on one side, hashes each of that side's keys in
the active units into one of n bins and leaves, per unit, the parity bit
and the 32-bit XOR sum of every bin.  The least it has to move is

* every key read once: 4 B a key;
* each unit's bin seed read once: 4 B a unit;
* per unit, n bins of (1 parity bit + 32-bit XOR sum) written: 33 n / 8 B.

These counts come from the round's shapes (units, keys, n), never from how
a kernel computes them: the one-hot MXU formulation's O(n) work per key,
its padded rows and its tile size add nothing here, so a kernel that does
less wasted work can only raise its share.  Binning is bound by these
bytes: its arithmetic (one 32-bit hash and one range reduction per key) is
a few integer operations per 4 B read.
"""
import re

# the binning kernel's ops as the device trace names them
TRACE_NAME = re.compile(r"_units_kernel|bin_parity_xorsum")


def bytes_needed(units: int, keys: int, n: int) -> float:
    return 4.0 * keys + 4.0 * units + units * n * 33 / 8


def least_seconds(rounds, peaks: dict) -> float:
    """Least device seconds for ``rounds``, each (units, keys_a, keys_b, n):
    both sides of every round, at the chip's HBM bandwidth."""
    total = sum(
        bytes_needed(u, ka, n) + bytes_needed(u, kb, n)
        for u, ka, kb, n in rounds
    )
    return total / peaks["hbm_byte_per_s"]


def device_seconds(op_s: dict) -> float:
    """The kernel's device seconds among a trace's per-op times."""
    return sum(s for name, s in op_s.items() if TRACE_NAME.search(name))
