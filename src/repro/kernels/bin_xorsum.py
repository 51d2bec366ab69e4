"""Hash-partition + parity bitmap + per-bin XOR fold, as one Pallas kernel.

The CPU algorithm scatters each element into its hash bin (sequential memory
chaos); the TPU formulation (DESIGN.md §3) makes it dense algebra: for an
element tile E, with H = one_hot(bin(E)) ∈ {0,1}^(tile × n) and
bits(E) ∈ {0,1}^(tile × 33) (32 key bits ‖ ones column for counting),

    acc(n × 33) += Hᵀ @ bits(E)        — one MXU matmul per tile,

then `acc & 1` yields per-bin XOR folds (bit-parity == XOR) and the parity
bitmap (count parity) in one shot.  The grid walks element tiles; `acc`
lives in VMEM scratch for the whole pass.  The batched kernel keeps keys on
the lane axis and accumulates the transpose, bitsᵀ (33 × tile) against Hᵀ
(n × tile) contracted over lanes into (33 × n), with bf16 0/1 operands;
it folds the 32 bit planes into finished uint32 words before writing, so
its outputs are lane-dense (U, n) arrays, bins on lanes, 8 units to a tile.

Two binning reductions are provided (both keyed by murmur-finalizer mix32):

* ``bin_parity_xorsum`` (single set) reduces with `mod n` — the historical
  kernel hash, mirrored by `ref.bin_parity_xorsum_ref`;
* ``bin_parity_xorsum_units`` (the batched multi-session path, DESIGN.md §5)
  reduces with the same multiply-shift `(h * n) >> 32` as
  `repro.core.hashing.hash_to_range`, so the kernel bins bit-for-bit like the
  numpy protocol.  The 64-bit product is synthesized from 16-bit halves
  (`mulshift_bins`) because TPU lanes are 32-bit; exact for any n < 2^16,
  which covers every field this repo instantiates (m ≤ 14).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import ceil_to, resolve_interpret


def mix32_jnp(x: jax.Array, seed) -> jax.Array:
    """murmur3 fmix32 (uint32 lanes, wrap-around multiplies) — VPU-only ops.

    ``seed`` may be a python int or a traced scalar (per-unit seeds).
    """
    x = x.astype(jnp.uint32)
    x = x + (jnp.asarray(seed, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def mulshift_bins(h: jax.Array, size: int) -> jax.Array:
    """Bias-free range reduction ``(h * size) >> 32`` in 32-bit lanes.

    Splits h into 16-bit halves so every partial product stays below 2^32;
    exact match of ``core.hashing.hash_to_range`` for size < 2^16.
    """
    assert size < (1 << 16), size
    lo = h & jnp.uint32(0xFFFF)
    hi = h >> jnp.uint32(16)
    sz = jnp.uint32(size)
    return ((hi * sz + ((lo * sz) >> jnp.uint32(16))) >> jnp.uint32(16)).astype(jnp.int32)


def _kernel(elems_ref, valid_ref, o_ref, acc_ref, *, n_bins: int, seed: int, nt: int):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e = elems_ref[...].astype(jnp.uint32)  # (tile,)
    valid = valid_ref[...] > 0
    h = mix32_jnp(e, seed)
    bins = (h % jnp.uint32(n_bins)).astype(jnp.int32)
    # one-hot dispatch matrix (tile, n) and bit matrix (tile, 33)
    onehot = (
        (bins[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, n_bins), 1))
        & valid[:, None]
    ).astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 32), 1)
    bits = ((e[:, None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    bits = jnp.concatenate([bits, valid[:, None].astype(jnp.int32)], axis=1)  # ‖ ones
    acc_ref[...] += jnp.dot(onehot.T, bits, preferred_element_type=jnp.int32)

    @pl.when(ti == nt - 1)
    def _emit():
        o_ref[...] = acc_ref[...] & 1


@functools.partial(jax.jit, static_argnames=("n_bins", "seed", "tile", "interpret"))
def bin_parity_xorsum(
    elems: jax.Array,
    *,
    n_bins: int,
    seed: int,
    tile: int = 1024,
    interpret: bool | None = None,
):
    """Returns (parity_bitmap (n,), xor_bits (n, 32)) for a set of uint32 keys."""
    interpret = resolve_interpret(interpret)
    e = elems.astype(jnp.uint32)
    E = e.shape[0]
    Ep = max(tile, ((E + tile - 1) // tile) * tile)
    pad = Ep - E
    e_p = jnp.concatenate([e, jnp.zeros(pad, jnp.uint32)])
    valid = jnp.concatenate([jnp.ones(E, jnp.int32), jnp.zeros(pad, jnp.int32)])
    nt = Ep // tile
    out = pl.pallas_call(
        functools.partial(_kernel, n_bins=n_bins, seed=seed, nt=nt),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((n_bins, 33), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_bins, 33), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n_bins, 33), jnp.int32)],
        interpret=interpret,
    )(e_p, valid)
    parity = out[:, 32]
    xor_bits = out[:, :32]
    return parity, xor_bits


# Units per output block of the batched kernel: its (8, n) output blocks
# fill the 8 sublanes of a tile, so no output row is padded.
UNITS_PER_BLOCK = 8


def _units_kernel(seeds_ref, elems_ref, valid_ref, parity_ref, xor_ref, acc_ref, *,
                  n_bins: int, nt: int):
    """Grid (U / 8, 8, nt): unit 8g + j walks its element tiles accumulating
    bitsᵀ @ H, then writes its finished words into row j of the (8, n)
    output blocks, which stay in VMEM while g is unchanged.

    Elements ride the lane axis as a ``(1, tile)`` row; the one-hot is
    built as ``(n, tile)`` and the bit planes as ``(33, tile)``, and the
    MXU contracts both over the lane axis into a ``(33, n)`` count: bit
    planes on sublanes, bins on lanes.  0/1 operands are exact in bf16
    and every per-tile count (≤ tile ≤ 1024) is exact in the f32
    accumulator, so the int32 running sum is bit-identical to integer math.
    ``_emit`` folds rows 0..31 (mod 2) into one 32-bit XOR word per bin
    with shifts and ORs, and row 32 (mod 2) is the parity bitmap.
    """
    g = pl.program_id(0)
    j = pl.program_id(1)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e = elems_ref[...].astype(jnp.uint32)      # (1, tile)
    valid = valid_ref[...] > 0                 # (1, tile)
    seed = seeds_ref[g * UNITS_PER_BLOCK + j].astype(jnp.uint32)  # from SMEM
    bins = mulshift_bins(mix32_jnp(e, seed), n_bins)
    hit = (jax.lax.broadcasted_iota(jnp.int32, (n_bins, 1), 0) == bins) & valid
    onehot = jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16)  # (n, tile)
    planes = jax.lax.broadcasted_iota(jnp.int32, (33, 1), 0)
    shifts = jnp.minimum(planes, 31).astype(jnp.uint32)
    key_bits = ((e >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    # rows 0..31: key bit planes; row 32: the ones row (valid) for counting
    bit = jnp.where(planes == 32, valid.astype(jnp.int32), key_bits)
    bits = bit.astype(jnp.float32).astype(jnp.bfloat16)
    counts = jax.lax.dot_general(
        bits, onehot, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (33, n)
    acc_ref[...] += counts.astype(jnp.int32)

    @pl.when(ti == nt - 1)
    def _emit():
        odd = acc_ref[...] & 1                                   # (33, n)
        words = odd[0:32, :] << planes[0:32]                     # plane r to bit r
        for half in (16, 8, 4, 2, 1):                            # OR-fold 32 -> 1
            words = words[:half, :] | words[half:, :]
        mine = jax.lax.broadcasted_iota(jnp.int32, (UNITS_PER_BLOCK, 1), 0) == j
        parity_ref[...] = jnp.where(mine, odd[32:33, :], parity_ref[...])
        xor_ref[...] = jnp.where(mine, words, xor_ref[...])


@functools.partial(jax.jit, static_argnames=("n_bins", "tile", "interpret"))
def bin_parity_xorsum_units(
    elems: jax.Array,
    valid: jax.Array,
    seeds: jax.Array,
    *,
    n_bins: int,
    tile: int | None = None,
    interpret: bool | None = None,
):
    """Batched bin/parity/XOR-fold over U packed units in one kernel launch.

    ``elems``/``valid``: (U, E) padded unit rows (valid == 0 marks padding);
    ``seeds``: (U,) uint32 per-unit binning seeds (sessions derive different
    seeds, so units of many sessions pack into one launch — DESIGN.md §5).
    Bins with the protocol's multiply-shift hash (``hash_to_range``).
    Returns (parity (U, n_bins) int32, xors (U, n_bins) uint32), lane-dense:
    bins on the lane axis, 8 units to a tile of sublanes.
    """
    interpret = resolve_interpret(interpret)
    e = elems.astype(jnp.uint32)
    U, E = e.shape
    if tile is None:  # smallest lane-aligned tile covering typical unit loads
        tile = max(128, min(1024, ceil_to(E, 128)))
    Ep = max(tile, ceil_to(E, tile))
    Up = ceil_to(U, UNITS_PER_BLOCK)
    pads = ((0, Up - U), (0, Ep - E))
    # (Up, 1, Ep) rows: a (1, tile) block spans the full second-minor dim
    e_p = jnp.pad(e, pads)[:, None, :]
    v_p = jnp.pad(valid.astype(jnp.int32), pads)[:, None, :]
    s_p = jnp.pad(jax.lax.bitcast_convert_type(seeds.astype(jnp.uint32), jnp.int32),
                  (0, Up - U))
    nt = Ep // tile
    row = pl.BlockSpec((None, 1, tile), lambda g, j, i, s: (g * UNITS_PER_BLOCK + j, 0, i))
    words = pl.BlockSpec((UNITS_PER_BLOCK, n_bins), lambda g, j, i, s: (g, 0))
    parity, xors = pl.pallas_call(
        functools.partial(_units_kernel, n_bins=n_bins, nt=nt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Up // UNITS_PER_BLOCK, UNITS_PER_BLOCK, nt),
            in_specs=[row, row],
            out_specs=[words, words],
            scratch_shapes=[pltpu.VMEM((33, n_bins), jnp.int32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((Up, n_bins), jnp.int32)] * 2,
        interpret=interpret,
    )(s_p, e_p, v_p)
    return parity[:U], jax.lax.bitcast_convert_type(xors[:U], jnp.uint32)


def xor_bits_to_u32(xor_bits: jax.Array) -> jax.Array:
    """(..., 32) 0/1 bit planes -> (...,) uint32 XOR-fold values."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(xor_bits.astype(jnp.uint32) << shifts, axis=-1, dtype=jnp.uint32)
