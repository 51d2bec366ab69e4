"""GF(2) dense matmul Pallas kernel — the MXU workhorse for PBS coding.

C = (A @ B) mod 2 for 0/1 matrices.  This single kernel implements
both BCH hot loops after the DESIGN.md §3 reformulation:

* **syndromes**:  sketches = (parity_bitmaps @ syndrome_matrix) mod 2
  with A = (groups, n) bitmaps, B = (n, t*m) precomputed powers-of-alpha bits;
* **Chien search**: evals = (locator_bits @ chien_matrix) mod 2
  with A = (groups, (t+1)*m), B = ((t+1)*m, n*m).

The operands are padded as int8 and the MXU accumulates in int32 (it takes
no int32 operands), which is exact for counts ≤ K < 2^31, so a single `& 1`
after the k loop gives the GF(2) product; interpret mode runs the same
dataflow on CPU.  Block shapes are hardware-aligned (lane dim multiples of
128, row blocks multiples of the 32-row int8 tile); the K (reduction) grid
axis is innermost so each (i, j) output tile accumulates in a VMEM scratch
across sequential k steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import ceil_to as _ceil_to
from .platform import resolve_interpret


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...] & 1  # sum mod 2 == XOR accumulation


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def gf2_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """(A @ B) mod 2 for 0/1 matrices of any shape; int32 result.

    Operands are padded to block multiples as int8 internally."""
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    # clamp block sizes to (padded) problem dims, keeping HW alignment
    bm_ = min(_ceil_to(bm, 32), _ceil_to(m, 32))
    bn_ = min(bn, _ceil_to(n, 128))
    bk_ = min(bk, _ceil_to(k, 128))
    mp, np_, kp = _ceil_to(m, bm_), _ceil_to(n, bn_), _ceil_to(k, bk_)
    a_p = jnp.zeros((mp, kp), jnp.int8).at[:m, :k].set(a.astype(jnp.int8))
    b_p = jnp.zeros((kp, np_), jnp.int8).at[:k, :n].set(b.astype(jnp.int8))
    nk = kp // bk_
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(mp // bm_, np_ // bn_, nk),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]
