"""Jitted wrappers tying the Pallas kernels to PBS protocol semantics.

* ``encode_group``       — parity bitmap + bin XOR folds + BCH sketch for one
                           set (bin_xorsum kernel + gf2_matmul).
* ``encode_groups``      — the batched form over U packed units with ragged
                           element counts (padded rows + valid masks) and
                           per-unit bin seeds, binning with the protocol's
                           multiply-shift hash.  The multi-session engine's
                           fused executor (DESIGN.md §5) composes the same
                           two pieces — ``bin_parity_xorsum_units`` +
                           ``sketch_groups`` — over both sides at once.
* ``bch_decode_batched`` — fully-jitted vmapped Berlekamp–Massey + Chien
                           search over all group pairs at once (fixed 2t-trip
                           ``fori_loop``; the TPU replacement for the paper's
                           serial per-group Levinson decode — DESIGN.md §3).
* ``tow_estimate``       — ToW sketches via the tow_sketch kernel.

Everything is validated against `ref.py` / `repro.core.bch` in
tests/test_kernels.py and tests/test_recon_batch.py across shape/dtype
sweeps.  ``interpret=None`` resolves per backend (kernels/platform.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bch import BCHCode, bch_code
from repro.core.gf2m import get_field

from .bin_xorsum import bin_parity_xorsum, bin_parity_xorsum_units, xor_bits_to_u32
from .gf2_matmul import gf2_matmul
from .platform import count_retrace
from .tow_sketch import tow_sketch


def _xor_reduce(x: jax.Array, axis: int) -> jax.Array:
    return jax.lax.reduce(x, np.int32(0), jax.lax.bitwise_xor, (axis,))


def pack_bits_to_field(bits: jax.Array, m: int) -> jax.Array:
    """(..., t*m) 0/1 -> (..., t) integer field elements (LSB-first)."""
    t = bits.shape[-1] // m
    b = bits.reshape(bits.shape[:-1] + (t, m)).astype(jnp.int32)
    return jnp.sum(b << jnp.arange(m, dtype=jnp.int32), axis=-1)


def sketch_groups(bitmaps: jax.Array, code: BCHCode, *, interpret: bool | None = None):
    """BCH sketches for G parity bitmaps at once: one GF(2) matmul on the MXU."""
    P = jnp.asarray(code.field.syndrome_matrix(code.t))
    bits = gf2_matmul(bitmaps.astype(jnp.int32), P, interpret=interpret)
    return pack_bits_to_field(bits, code.m)


def sketch_groups_range(
    bitmaps: jax.Array, code: BCHCode, t0: int, *, interpret: bool | None = None
):
    """Incremental BCH syndromes S_{2*t0+1}..S_{2t-1} for G parity bitmaps.

    The same one-matmul formulation as ``sketch_groups`` against the
    ``[t0*m, t*m)`` column slice of the syndrome matrix — the prefix
    property (``core.gf2m.syndrome_matrix_range``) guarantees
    ``concat(sketch at t0, this) == sketch at t`` bit for bit, which is
    what ``MSG_PARITY`` ships on rateless recovery (DESIGN.md §16).
    """
    P = jnp.asarray(code.field.syndrome_matrix_range(t0, code.t))
    bits = gf2_matmul(bitmaps.astype(jnp.int32), P, interpret=interpret)
    return pack_bits_to_field(bits, code.m)


def encode_group(elems: jax.Array, code: BCHCode, seed: int, *, interpret: bool | None = None):
    """Full PBS encode of one group: (parity bitmap, bin XOR sums, sketch)."""
    parity, xor_bits = bin_parity_xorsum(
        elems, n_bins=code.n, seed=seed, interpret=interpret
    )
    sketch = sketch_groups(parity[None, :], code, interpret=interpret)[0]
    return parity, xor_bits_to_u32(xor_bits), sketch


def encode_groups(
    elems: jax.Array,
    valid: jax.Array,
    seeds: jax.Array,
    code: BCHCode,
    *,
    interpret: bool | None = None,
):
    """Batched PBS encode of U packed units with ragged element counts.

    ``elems``/``valid``: (U, E) padded rows (``valid == 0`` marks padding);
    ``seeds``: (U,) per-unit bin seeds.  One bin_xorsum launch bins every
    unit's elements with the protocol's multiply-shift hash, then one GF(2)
    matmul sketches all parity bitmaps (DESIGN.md §5).

    Returns (parity (U, n), xors (U, n) uint32, sketches (U, t)).
    """
    parity, xors = bin_parity_xorsum_units(
        elems, valid, seeds, n_bins=code.n, interpret=interpret
    )
    sketches = sketch_groups(parity, code, interpret=interpret)
    return parity, xors, sketches


def tow_estimate(elems_a: jax.Array, elems_b: jax.Array, seeds: jax.Array, *, interpret=None):
    ya = tow_sketch(elems_a, seeds, ell=seeds.shape[0], interpret=interpret)
    yb = tow_sketch(elems_b, seeds, ell=seeds.shape[0], interpret=interpret)
    diff = (ya - yb).astype(jnp.float32)
    return jnp.mean(diff * diff)


# ---------------------------------------------------------------------------
# Batched BCH decode, fully in JAX (jit + vmap over group pairs)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "t"))
def bch_decode_batched(sketches: jax.Array, *, n: int, t: int):
    """Decode U difference sketches -> (ok (U,), positions (U, t), count (U,)).

    positions rows are padded with -1 beyond `count`.  ok=False marks BCH
    overload (paper §3.2 -> 3-way split).  GF ops run on log/exp tables in
    int32 lanes; BM is a fixed-trip fori_loop (no data-dependent control).
    """
    count_retrace("bch_decode_batched")
    code = bch_code(n, t)
    gf = code.field
    m = code.m
    exp_t = jnp.asarray(gf.exp, dtype=jnp.int32)          # (2n,)
    log_t = jnp.asarray(np.where(gf.log < 0, 0, gf.log), dtype=jnp.int32)

    def gmul(a, b):
        prod = exp_t[(log_t[a] + log_t[b]) % n]
        return jnp.where((a == 0) | (b == 0), 0, prod)

    def ginv(a):
        return exp_t[(n - log_t[a]) % n]

    sk = sketches.astype(jnp.int32)
    U = sk.shape[0]

    # S_1..S_2t with S_2k = S_k^2
    S = jnp.zeros((U, 2 * t), jnp.int32)
    S = S.at[:, 0::2].set(sk)
    for k in range(1, t + 1):  # unrolled t steps; t is static & small
        S = S.at[:, 2 * k - 1].set(gmul(S[:, k - 1], S[:, k - 1]))

    W = 2 * t + 1
    cols = jnp.arange(W)

    def bm_step(i, state):
        C, B, L, b, mshift = state
        j = jnp.arange(1, W)
        s_idx = jnp.clip(i - j, 0, 2 * t - 1)
        gath = S[:, s_idx]                                  # (U, W-1)
        mask = (j[None, :] <= i) & (j[None, :] <= L[:, None])
        d = S[:, i] ^ _xor_reduce(jnp.where(mask, gmul(C[:, 1:], gath), 0), 1)

        nz = d != 0
        grow = nz & (2 * L <= i)
        coef = jnp.where(nz, gmul(d, ginv(jnp.where(b == 0, 1, b))), 0)
        idx = cols[None, :] - mshift[:, None]
        Bsh = jnp.where(
            idx >= 0, jnp.take_along_axis(B, jnp.clip(idx, 0, W - 1), 1), 0
        )
        Cnew = C ^ gmul(jnp.broadcast_to(coef[:, None], Bsh.shape), Bsh)

        B2 = jnp.where(grow[:, None], C, B)
        C2 = jnp.where(nz[:, None], Cnew, C)
        b2 = jnp.where(grow, d, b)
        L2 = jnp.where(grow, i + 1 - L, L)
        m2 = jnp.where(grow, 1, mshift + 1)
        return (C2, B2, L2, b2, m2)

    C0 = jnp.zeros((U, W), jnp.int32).at[:, 0].set(1)
    B0 = jnp.zeros((U, W), jnp.int32).at[:, 0].set(1)
    state = (C0, B0, jnp.zeros(U, jnp.int32), jnp.ones(U, jnp.int32), jnp.ones(U, jnp.int32))
    C, B, L, b, mshift = jax.lax.fori_loop(0, 2 * t, bm_step, state)

    # Chien search: evaluate Lambda at alpha^{-i} for all i (Horner, t+1 steps)
    ii = jnp.arange(n)
    xs = exp_t[(-ii) % n]                                    # (n,)
    acc = jnp.zeros((U, n), jnp.int32)
    for k in range(t, -1, -1):
        acc = gmul(acc, xs[None, :]) ^ C[:, k : k + 1]
    is_root = acc == 0                                       # (U, n)
    count = jnp.sum(is_root, axis=1)

    # gather root positions, padded with -1
    key = jnp.where(is_root, ii[None, :], n + 1)
    pos = jnp.sort(key, axis=1)[:, :t]
    pos = jnp.where(jnp.arange(t)[None, :] < count[:, None], pos, -1)

    # verify: recompute odd syndromes from found roots
    jj = jnp.arange(t)
    powers = (jnp.maximum(pos, 0)[:, :, None] * (2 * jj + 1)[None, None, :]) % n
    vals = jnp.where((pos >= 0)[:, :, None], exp_t[powers], 0)  # (U, t, t)
    recomputed = _xor_reduce(vals, 1)                           # (U, t)

    zero_sk = ~jnp.any(sk != 0, axis=1)
    ok = (
        (L > 0)
        & (L <= t)
        & (count == L)
        & jnp.all(recomputed == sk, axis=1)
    ) | zero_sk
    # failed or empty rows expose no positions (matches core.bch semantics)
    expose = ok & ~zero_sk
    count = jnp.where(expose, count, 0)
    pos = jnp.where(expose[:, None], pos, -1)
    return ok, pos, count


def chien_eval_matmul(locator_bits: jax.Array, code: BCHCode, *, interpret=None):
    """Whole-field locator evaluation as one GF(2) matmul (kernel path).

    locator_bits: (U, (t+1)*m) -> eval bits (U, n, m); rows of zeros = roots.
    """
    Cmat = jnp.asarray(code.field.chien_matrix(code.t))
    ev = gf2_matmul(locator_bits.astype(jnp.int32), Cmat, interpret=interpret)
    return ev.reshape(ev.shape[0], code.n, code.m)
