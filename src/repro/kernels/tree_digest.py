"""Batched per-range ToW digest Pallas kernel for the tree front end (§15).

One launch digests a whole tree-level frontier: the caller packs each
range's elements into one row of a padded ``(R, E)`` matrix with a 0/1
valid mask, and the kernel emits the ``(R, ell)`` sketch matrix — the
``tow_sketch`` accumulator pattern lifted to a 2-D grid ``(R, E/tile)``
where the element axis iterates fastest, so each range's VMEM accumulator
is initialized at its first tile and emitted at its last before the grid
advances to the next range.  Same hash family as phase 0
(``mix32(mix32(e, 0x5EED) ^ seed, 0x7077)``), so a single-range frontier
reproduces ``tow_sketch`` exactly; the host oracle lives in
``repro.tree.partition.level_digests_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bin_xorsum import mix32_jnp
from .platform import count_retrace, resolve_interpret


def _kernel(elems_ref, valid_ref, seeds_ref, o_ref, acc_ref, *, nt: int):
    ti = pl.program_id(1)  # element-tile axis: minor, iterates fastest

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e = elems_ref[...].astype(jnp.uint32)  # (tile,)
    valid = valid_ref[...].astype(jnp.int32)  # (tile,)
    seeds = seeds_ref[...].astype(jnp.uint32)  # (ell,)
    h1 = mix32_jnp(e, 0x5EED)[:, None]  # (tile, 1)
    h = mix32_jnp(h1 ^ seeds[None, :], 0x7077)  # (tile, ell)
    signs = 1 - 2 * (h & jnp.uint32(1)).astype(jnp.int32)
    signs = signs * valid[:, None]
    acc_ref[...] += jnp.sum(signs, axis=0, keepdims=True)

    @pl.when(ti == nt - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("ell", "tile", "interpret"))
def tree_digest(
    elems: jax.Array,
    valid: jax.Array,
    seeds: jax.Array,
    *,
    ell: int = 32,
    tile: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-range ToW sketches: ``(R, E)`` padded rows -> ``(R, ell)``.

    ``elems``/``valid`` must already be padded to the caller's shape
    buckets (``pow2_bucket`` rows and row length, DESIGN.md §12) so the jit
    signature depends only on the bucket, never the frontier; rows narrower
    than ``tile`` are padded up to one tile here.
    """
    count_retrace("tree_digest")
    interpret = resolve_interpret(interpret)
    e = elems.astype(jnp.uint32)
    R, E = e.shape
    Ep = max(tile, ((E + tile - 1) // tile) * tile)
    pad = Ep - E
    if pad:
        e = jnp.pad(e, ((0, 0), (0, pad)))
        valid = jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, pad)))
    nt = Ep // tile
    # (R, 1, E) views: a squeezed (1, tile) block spans the full second-minor
    # dim, which the TPU's (8, 128) block rule accepts; (1, ell) rows out
    out = pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid=(R, nt),
        in_specs=[
            pl.BlockSpec((None, None, tile), lambda r, i: (r, 0, i)),
            pl.BlockSpec((None, None, tile), lambda r, i: (r, 0, i)),
            pl.BlockSpec((ell,), lambda r, i: (0,)),
        ],
        out_specs=pl.BlockSpec((None, 1, ell), lambda r, i: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1, ell), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, ell), jnp.int32)],
        interpret=interpret,
    )(e[:, None, :], valid.astype(jnp.int32)[:, None, :], seeds.astype(jnp.uint32))
    return out[:, 0, :]
