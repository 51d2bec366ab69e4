"""The fused round executor: one cohort's round as one jitted device call.

Per call (DESIGN.md §5 round dataflow), for all U packed units at once:

1. **on-device row build** — gather each unit's element row from the
   cohort's resident store (uploaded once per run), derive the valid mask
   from the store counts, apply Alice's diff overlay (drop removed = A ∩ D̂
   by value match, append added = D̂ \\ A columns), and mask both sides by
   the unit's 3-way-split filter chain with the same multiply-shift hash
   the protocol uses on the host;
2. **fused two-side encode** — Alice's and Bob's built rows stack into ONE
   ``bin_parity_xorsum_units`` launch and ONE GF(2) sketch matmul (half the
   kernel launches of encoding each side separately), with the per-unit
   wrap-around checksums folded into the same pass;
3. the sketch XOR feeds ``bch_decode_batched`` — the vmapped fixed-trip
   Berlekamp–Massey + Chien search (DESIGN.md §3) — locating each unit's
   differing bins (``ok`` False = BCH overload → the host re-queues the
   unit's 3-way split).

Shape polymorphism is confined to (U, Wa, Wb, R, X, F), all bucketed to
powers of two by the planner, so a serving loop settles into a bounded set
of compiled variants per cohort code.  The per-round overlay buffers are
not donated: they are a few KB, and most of them match no output shape, so
XLA could not alias them anyway.

``encode_side`` is the single-side half of the same pass — one endpoint's
row build + bin/sketch/checksum without the other side or the decode — used
by the ``repro.net`` wire endpoints (DESIGN.md §9), which ship the sketches
as frames and (on Bob's end) feed the frame-decoded XOR to the batched
decoder.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bch import bch_code
from repro.kernels.bin_xorsum import bin_parity_xorsum_units, mix32_jnp, mulshift_bins
from repro.kernels.ops import bch_decode_batched, sketch_groups, sketch_groups_range
from repro.kernels.platform import count_retrace
from repro.obs.trace import current_tracer, set_tracer

# Opt-in profiler hook (DESIGN.md §14): with a Tracer built with
# jax_profiler=True installed process-wide, every executor dispatch window
# is annotated inside a ``jax.profiler.trace`` capture.  With none
# installed NULL_TRACER hands back a shared no-op context, so the
# un-opted path costs one with-statement.
set_dispatch_tracer = set_tracer


def readback(out, what: str, tracer=None):
    """Wait for the device outputs ``out`` and copy them to the host, under
    the ``device.readback`` span: the one ``cat="device"`` span, so a trace
    tells host time blocked on the chip from the host work around it.  Its
    ``bytes`` arg is what was copied."""
    tracer = tracer if tracer is not None else current_tracer()
    with tracer.span("device.readback", cat="device", what=what) as span:
        host = jax.device_get(out)
        span.set(bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(host)))
        return host


def _count_trace(name: str, probe) -> None:
    """Ledger one jit trace of this executor (DESIGN.md §12).

    The body of a jitted function runs exactly once per cache-missing
    signature; the Tracer guard keeps eager (un-jitted) calls of the same
    body — the kernel unit tests — out of the serving-loop retrace count.
    """
    if isinstance(probe, jax.core.Tracer):
        count_retrace(name)


def _wrap_csum(elems: jax.Array, valid: jax.Array) -> jax.Array:
    """Per-unit checksum c(S) = sum mod 2^32 via wrap-around uint32 adds."""
    vals = jnp.where(valid, elems.astype(jnp.uint32), jnp.uint32(0))
    return jnp.sum(vals, axis=1, dtype=jnp.uint32)


def _build_rows(flat, start, cnt, row_map, width: int):
    """Gather padded unit element rows + validity from the CSR store.

    ``width`` is the planner's per-round gather width (pow2-bucketed max row
    count among the gathered units); reads past a row's count are clamped to
    index 0 and masked invalid.
    """
    starts = start[row_map][:, None]                   # (U, 1)
    counts = cnt[row_map][:, None]
    offs = jnp.arange(width, dtype=jnp.int32)[None, :]
    valid = offs < counts
    idx = jnp.where(valid, starts + offs, 0)
    return flat[idx], valid                            # (U, W) uint32, bool


def _apply_filters(elems, valid, fseeds, fbins, fcnt):
    """Mask elements by the unit's 3-way-split filter chain (paper §3.2).

    F (the chain depth) is a static dim, so the loop unrolls; inactive
    levels (fcnt <= k) pass everything through.
    """
    for k in range(fseeds.shape[1]):
        on = (fcnt > k)[:, None]
        bins3 = mulshift_bins(mix32_jnp(elems, fseeds[:, k][:, None]), 3)
        valid = valid & (~on | (bins3 == fbins[:, k][:, None]))
    return valid


def _build_side(
    flat, start, cnt, row_map, width, removed, removed_cnt, added, added_cnt,
    unit_valid, fseeds, fbins, fcnt,
):
    """One side's full on-device unit-row build: CSR gather, diff overlay
    (drop ``removed`` by value match, append ``added`` columns — both may be
    zero-width, in which case the overlay ops vanish), split-filter chain,
    and the padding-unit mask.  Shared by the fused two-side executor and
    the single-side executor the wire endpoints drive."""
    e, v = _build_rows(flat, start, cnt, row_map, width)
    if removed.shape[1]:
        rm_on = jnp.arange(removed.shape[1])[None, :] < removed_cnt[:, None]
        hit = (e[:, :, None] == removed[:, None, :]) & rm_on[:, None, :]
        v = v & ~jnp.any(hit, axis=-1)
    if added.shape[1]:
        e = jnp.concatenate([e, added], axis=1)
        v = jnp.concatenate(
            [v, jnp.arange(added.shape[1])[None, :] < added_cnt[:, None]], axis=1
        )
    v = _apply_filters(e, v, fseeds, fbins, fcnt)
    return e, v & (unit_valid != 0)[:, None]


def _pad_width(elems, valid, width):
    pad = width - elems.shape[1]
    if pad == 0:
        return elems, valid
    return (
        jnp.pad(elems, ((0, 0), (0, pad))),
        jnp.pad(valid, ((0, 0), (0, pad))),
    )


def _execute_round(
    flat_a: jax.Array,
    start_a: jax.Array,
    cnt_a: jax.Array,
    flat_b: jax.Array,
    start_b: jax.Array,
    cnt_b: jax.Array,
    row_map: jax.Array,
    unit_valid: jax.Array,
    seeds: jax.Array,
    removed: jax.Array,
    removed_cnt: jax.Array,
    added: jax.Array,
    added_cnt: jax.Array,
    fseeds: jax.Array,
    fbins: jax.Array,
    fcnt: jax.Array,
    *,
    n: int,
    t: int,
    width_a: int,
    width_b: int,
    interpret: bool | None = None,
):
    """Run one PBS round for U packed units of one (n, t) cohort.

    Returns (xors_a, xors_b (U, n) uint32, ok (U,), positions (U, t) padded
    with -1, counts (U,), csum_a, csum_b (U,) uint32).
    """
    _count_trace("execute_round", flat_a)
    code = bch_code(n, t)
    empty_overlay = jnp.zeros((row_map.shape[0], 0), jnp.uint32)
    zero_cnt = jnp.zeros(row_map.shape[0], jnp.int32)

    # --- Alice: store row + diff overlay; Bob: store row only -----------
    ea, va = _build_side(
        flat_a, start_a, cnt_a, row_map, width_a,
        removed, removed_cnt, added, added_cnt, unit_valid, fseeds, fbins, fcnt,
    )
    eb, vb = _build_side(
        flat_b, start_b, cnt_b, row_map, width_b,
        empty_overlay, zero_cnt, empty_overlay, zero_cnt,
        unit_valid, fseeds, fbins, fcnt,
    )

    # --- fused two-side encode: one bin launch, one sketch matmul -------
    width = max(ea.shape[1], eb.shape[1])
    ea, va = _pad_width(ea, va, width)
    eb, vb = _pad_width(eb, vb, width)
    elems2 = jnp.concatenate([ea, eb], axis=0)          # (2U, W)
    valid2 = jnp.concatenate([va, vb], axis=0)
    seeds2 = jnp.concatenate([seeds, seeds], axis=0)
    parity2, xors2 = bin_parity_xorsum_units(
        elems2, valid2.astype(jnp.int32), seeds2, n_bins=n, interpret=interpret
    )
    sk2 = sketch_groups(parity2, code, interpret=interpret)
    csum2 = _wrap_csum(elems2, valid2)

    u = row_map.shape[0]
    sk_diff = sk2[:u] ^ sk2[u:]
    ok, pos, cnt = bch_decode_batched(sk_diff, n=n, t=t)
    # sk_diff rides back with the outcomes: it is the cached syndrome
    # *prefix* the rateless recovery path (DESIGN.md §16) concatenates with
    # incremental parity when a unit overloads — nothing re-encodes.
    return xors2[:u], xors2[u:], ok, pos, cnt, csum2[:u], csum2[u:], sk_diff


def _encode_side(
    flat: jax.Array,
    start: jax.Array,
    cnt: jax.Array,
    row_map: jax.Array,
    unit_valid: jax.Array,
    seeds: jax.Array,
    removed: jax.Array,
    removed_cnt: jax.Array,
    added: jax.Array,
    added_cnt: jax.Array,
    fseeds: jax.Array,
    fbins: jax.Array,
    fcnt: jax.Array,
    *,
    n: int,
    t: int,
    width: int,
    interpret: bool | None = None,
):
    """Encode ONE side's U packed units: the wire-endpoint half of the round.

    Same on-device row build + bin/sketch/checksum pass as the fused
    executor, but for a single endpoint's resident store (Bob passes
    zero-width overlays).  Returns (sketches (U, t), xors (U, n) uint32,
    csum (U,) uint32); the sketches are what ``repro.wire`` bit-packs into
    the round frames, and Bob feeds the frame-decoded XOR of both sides'
    sketches to ``bch_decode_batched``.
    """
    _count_trace("encode_side", flat)
    code = bch_code(n, t)
    e, v = _build_side(
        flat, start, cnt, row_map, width,
        removed, removed_cnt, added, added_cnt, unit_valid, fseeds, fbins, fcnt,
    )
    parity, xors = bin_parity_xorsum_units(
        e, v.astype(jnp.int32), seeds, n_bins=n, interpret=interpret
    )
    sk = sketch_groups(parity, code, interpret=interpret)
    return sk, xors, _wrap_csum(e, v)


def _execute_round_ext(
    flat_a: jax.Array,
    start_a: jax.Array,
    cnt_a: jax.Array,
    flat_b: jax.Array,
    start_b: jax.Array,
    cnt_b: jax.Array,
    row_map: jax.Array,
    unit_valid: jax.Array,
    seeds: jax.Array,
    removed: jax.Array,
    removed_cnt: jax.Array,
    added: jax.Array,
    added_cnt: jax.Array,
    fseeds: jax.Array,
    fbins: jax.Array,
    fcnt: jax.Array,
    *,
    n: int,
    t0: int,
    t1: int,
    width_a: int,
    width_b: int,
    interpret: bool | None = None,
):
    """One rateless extension step for U packed units of one (n, t) cohort
    (DESIGN.md §16): rebuild both sides' rows for the SAME round (identical
    bin seeds → identical parity bitmaps) and emit only the XOR of the
    *incremental* syndromes S_{2*t0+1}..S_{2*t1-1} — a (U, t1-t0) array the
    host concatenates onto the cached round-diff prefix and decodes at t1.
    """
    _count_trace("execute_round_ext", flat_a)
    code = bch_code(n, t1)
    empty_overlay = jnp.zeros((row_map.shape[0], 0), jnp.uint32)
    zero_cnt = jnp.zeros(row_map.shape[0], jnp.int32)
    ea, va = _build_side(
        flat_a, start_a, cnt_a, row_map, width_a,
        removed, removed_cnt, added, added_cnt, unit_valid, fseeds, fbins, fcnt,
    )
    eb, vb = _build_side(
        flat_b, start_b, cnt_b, row_map, width_b,
        empty_overlay, zero_cnt, empty_overlay, zero_cnt,
        unit_valid, fseeds, fbins, fcnt,
    )
    width = max(ea.shape[1], eb.shape[1])
    ea, va = _pad_width(ea, va, width)
    eb, vb = _pad_width(eb, vb, width)
    elems2 = jnp.concatenate([ea, eb], axis=0)
    valid2 = jnp.concatenate([va, vb], axis=0)
    seeds2 = jnp.concatenate([seeds, seeds], axis=0)
    parity2, _ = bin_parity_xorsum_units(
        elems2, valid2.astype(jnp.int32), seeds2, n_bins=n, interpret=interpret
    )
    inc2 = sketch_groups_range(parity2, code, t0, interpret=interpret)
    u = row_map.shape[0]
    return inc2[:u] ^ inc2[u:]


def _encode_side_ext(
    flat: jax.Array,
    start: jax.Array,
    cnt: jax.Array,
    row_map: jax.Array,
    unit_valid: jax.Array,
    seeds: jax.Array,
    removed: jax.Array,
    removed_cnt: jax.Array,
    added: jax.Array,
    added_cnt: jax.Array,
    fseeds: jax.Array,
    fbins: jax.Array,
    fcnt: jax.Array,
    *,
    n: int,
    t0: int,
    t1: int,
    width: int,
    interpret: bool | None = None,
):
    """ONE side's incremental syndromes for the current round: the
    ``encode_side`` variant behind ``MSG_PARITY`` (DESIGN.md §16).  Same
    on-device row build and bin pass over the same round seeds, but the
    sketch matmul covers only syndrome columns [t0, t1) — Alice frames the
    result; Bob XORs his own against the frame and decodes at t1 with the
    cached prefix.  Returns (U, t1-t0) field elements.
    """
    _count_trace("encode_side_ext", flat)
    code = bch_code(n, t1)
    e, v = _build_side(
        flat, start, cnt, row_map, width,
        removed, removed_cnt, added, added_cnt, unit_valid, fseeds, fbins, fcnt,
    )
    parity, _ = bin_parity_xorsum_units(
        e, v.astype(jnp.int32), seeds, n_bins=n, interpret=interpret
    )
    return sketch_groups_range(parity, code, t0, interpret=interpret)


@functools.lru_cache(maxsize=None)
def _jitted_executor():
    return jax.jit(
        _execute_round,
        static_argnames=("n", "t", "width_a", "width_b", "interpret"),
    )


def execute_round(*args, **kwargs):
    """Jitted ``_execute_round``."""
    with current_tracer().annotate("repro.execute_round"):
        return _jitted_executor()(*args, **kwargs)


@functools.lru_cache(maxsize=None)
def _jitted_side_executor():
    return jax.jit(_encode_side, static_argnames=("n", "t", "width", "interpret"))


def encode_side(*args, **kwargs):
    """Jitted ``_encode_side`` (the per-endpoint half of ``execute_round``)."""
    with current_tracer().annotate("repro.encode_side"):
        return _jitted_side_executor()(*args, **kwargs)


# Extension executors: (n, t0, t1) are static — the deterministic t-ladder
# keeps the signature set bounded, so a warm serving loop extends with zero
# retraces (DESIGN.md §16).


@functools.lru_cache(maxsize=None)
def _jitted_ext_executor():
    return jax.jit(
        _execute_round_ext,
        static_argnames=("n", "t0", "t1", "width_a", "width_b", "interpret"),
    )


def execute_round_ext(*args, **kwargs):
    """Jitted ``_execute_round_ext`` (both sides' incremental syndrome XOR)."""
    with current_tracer().annotate("repro.execute_round_ext"):
        return _jitted_ext_executor()(*args, **kwargs)


@functools.lru_cache(maxsize=None)
def _jitted_side_ext_executor():
    return jax.jit(
        _encode_side_ext, static_argnames=("n", "t0", "t1", "width", "interpret")
    )


def encode_side_ext(*args, **kwargs):
    """Jitted ``_encode_side_ext`` (one endpoint's ``MSG_PARITY`` payload)."""
    with current_tracer().annotate("repro.encode_side_ext"):
        return _jitted_side_ext_executor()(*args, **kwargs)
