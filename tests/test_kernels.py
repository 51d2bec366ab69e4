"""Pallas kernel validation: shape/dtype sweeps vs pure-numpy oracles
(interpret mode executes the kernel body on CPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.bch import BCHCode, batched_decode, sketch_from_positions
from repro.core.pbs import unit_tables
from repro.kernels import ref
from repro.kernels.bin_xorsum import (
    bin_parity_xorsum,
    bin_parity_xorsum_units,
    xor_bits_to_u32,
)
from repro.kernels.gf2_matmul import gf2_matmul
from repro.kernels.ops import (
    bch_decode_batched,
    chien_eval_matmul,
    encode_group,
    pack_bits_to_field,
    sketch_groups,
    tow_estimate,
)
from repro.kernels.tow_sketch import tow_sketch
from repro.kernels.tree_digest import tree_digest


@pytest.mark.parametrize(
    "m,k,n",
    [
        (1, 127, 91),       # single bitmap x syndrome matrix
        (8, 255, 88),       # group batch
        (17, 511, 153),
        (64, 1023, 110),
        (3, 2047, 187),
        (130, 300, 260),    # non-power-of-two everything
        (5, 64, 640),
    ],
)
def test_gf2_matmul_sweep(m, k, n):
    rng = np.random.default_rng(m * 1000 + n)
    a = rng.integers(0, 2, (m, k)).astype(np.int32)
    b = rng.integers(0, 2, (k, n)).astype(np.int32)
    out = np.array(gf2_matmul(jnp.array(a), jnp.array(b)))
    np.testing.assert_array_equal(out, ref.gf2_matmul_ref(a, b))


@pytest.mark.parametrize("bm,bn,bk", [(8, 128, 128), (64, 256, 256), (128, 128, 512)])
def test_gf2_matmul_block_shapes(bm, bn, bk):
    rng = np.random.default_rng(bm)
    a = rng.integers(0, 2, (100, 700)).astype(np.int32)
    b = rng.integers(0, 2, (700, 200)).astype(np.int32)
    out = np.array(gf2_matmul(jnp.array(a), jnp.array(b), bm=bm, bn=bn, bk=bk))
    np.testing.assert_array_equal(out, ref.gf2_matmul_ref(a, b))


@pytest.mark.parametrize("n_bins", [63, 127, 255, 1023])
@pytest.mark.parametrize("n_elems", [1, 100, 1000, 5000])
def test_bin_parity_xorsum_sweep(n_bins, n_elems):
    rng = np.random.default_rng(n_bins + n_elems)
    elems = rng.integers(1, 1 << 32, size=n_elems, dtype=np.uint64).astype(np.uint32)
    parity, xor_bits = bin_parity_xorsum(jnp.array(elems), n_bins=n_bins, seed=42)
    p_ref, xb_ref, xors_ref = ref.bin_parity_xorsum_ref(elems, n_bins, 42)
    np.testing.assert_array_equal(np.array(parity), p_ref)
    np.testing.assert_array_equal(np.array(xor_bits), xb_ref)
    np.testing.assert_array_equal(np.array(xor_bits_to_u32(xor_bits)), xors_ref)


@pytest.mark.parametrize("tile", [256, 1024])
def test_bin_xorsum_tile_invariance(tile):
    rng = np.random.default_rng(0)
    elems = rng.integers(1, 1 << 32, size=3000, dtype=np.uint64).astype(np.uint32)
    p1, x1 = bin_parity_xorsum(jnp.array(elems), n_bins=127, seed=7, tile=tile)
    p_ref, xb_ref, _ = ref.bin_parity_xorsum_ref(elems, 127, 7)
    np.testing.assert_array_equal(np.array(p1), p_ref)
    np.testing.assert_array_equal(np.array(x1), xb_ref)


def _ragged_rows(rng, rows: int, width: int):
    """(rows, width) uint32 keys with a random 0/1 valid prefix per row
    (some rows empty, some full) — the padded-row layout the engine feeds."""
    elems = rng.integers(1, 1 << 32, size=(rows, width), dtype=np.uint64).astype(np.uint32)
    counts = rng.integers(0, width + 1, size=rows)
    counts[0], counts[-1] = 0, width
    valid = (np.arange(width)[None, :] < counts[:, None]).astype(np.int32)
    return elems, valid


@pytest.mark.parametrize(
    "units,width,n_bins",
    [
        (5, 37, 63),        # unit count not a multiple of 8; row < one tile
        (13, 300, 255),     # odd units, row spans a padded second tile
        (3, 1500, 1023),    # wide field (m = 10), two 1024-key tiles
    ],
)
def test_bin_units_layouts(units, width, n_bins):
    rng = np.random.default_rng(units * 7 + n_bins)
    elems, valid = _ragged_rows(rng, units, width)
    # seeds above 2^31 exercise the int32 bitcast through scalar memory
    seeds = rng.integers(0, 1 << 32, size=units, dtype=np.uint64).astype(np.uint32)
    seeds[0] = 0xFFFFFFFF
    parity, xors = bin_parity_xorsum_units(
        jnp.array(elems), jnp.array(valid), jnp.array(seeds), n_bins=n_bins
    )
    p_ref, x_ref = ref.bin_parity_xorsum_units_ref(elems, valid, seeds, n_bins)
    np.testing.assert_array_equal(np.array(parity), p_ref)
    np.testing.assert_array_equal(np.array(xors), x_ref)


@pytest.mark.parametrize("n_bins", [255, 511, 1023, 2047])
def test_bin_units_words_match_unit_tables(n_bins):
    """The lane-dense outputs, finished parity and XOR words per (unit,
    bin), equal the protocol's own tables (``core.pbs.unit_tables``) unit
    by unit: 11 units (one block of 8 and a padded second), ragged valid
    prefixes, invalid lanes holding keys, each unit on its own seed."""
    rng = np.random.default_rng(n_bins)
    units, width = 11, 700
    elems, valid = _ragged_rows(rng, units, width)
    valid[3, ::3] = 0                    # holes inside a row, not only a tail
    seeds = rng.integers(0, 1 << 32, size=units, dtype=np.uint64).astype(np.uint32)
    parity, xors = bin_parity_xorsum_units(
        jnp.array(elems), jnp.array(valid), jnp.array(seeds), n_bins=n_bins,
        interpret=True,
    )
    assert parity.shape == xors.shape == (units, n_bins)
    assert parity.dtype == jnp.int32 and xors.dtype == jnp.uint32
    parity, xors = np.asarray(parity), np.asarray(xors)
    for u in range(units):
        idx = np.flatnonzero(valid[u])
        slot, pos, x_ref, _ = unit_tables(
            elems[u], idx, np.zeros(len(idx), np.int64), 1, n_bins, int(seeds[u])
        )
        p_ref = np.zeros(n_bins, np.int32)
        p_ref[pos] = 1
        np.testing.assert_array_equal(parity[u], p_ref, err_msg=f"unit {u}")
        np.testing.assert_array_equal(xors[u], x_ref[0], err_msg=f"unit {u}")


@pytest.mark.parametrize("rows,width", [(3, 40), (9, 700)])
def test_tree_digest_layouts(rows, width):
    """Rows shorter than one tile and rows spanning two tiles, against the
    ToW oracle on each row's valid prefix."""
    rng = np.random.default_rng(rows * 31 + width)
    elems, valid = _ragged_rows(rng, rows, width)
    seeds = rng.integers(0, 1 << 32, size=32, dtype=np.uint64).astype(np.uint32)
    out = np.array(tree_digest(jnp.array(elems), jnp.array(valid), jnp.array(seeds), ell=32))
    expect = np.stack([
        ref.tow_sketch_ref(elems[r][valid[r] != 0], seeds) for r in range(rows)
    ])
    np.testing.assert_array_equal(out, expect)


@pytest.mark.parametrize("ell", [32, 128])
@pytest.mark.parametrize("n_elems", [5, 2048, 7001])
def test_tow_sketch_sweep(ell, n_elems):
    rng = np.random.default_rng(ell + n_elems)
    elems = rng.integers(1, 1 << 32, size=n_elems, dtype=np.uint64).astype(np.uint32)
    seeds = rng.integers(0, 1 << 32, size=ell, dtype=np.uint64).astype(np.uint32)
    out = np.array(tow_sketch(jnp.array(elems), jnp.array(seeds), ell=ell))
    np.testing.assert_array_equal(out, ref.tow_sketch_ref(elems, seeds))


def test_tow_kernel_variance_contract():
    """The kernel's hash family must honour the (2d^2-2d)/ell variance bound
    the paper's analysis needs (empirical check, ~1.5x tolerance)."""
    rng = np.random.default_rng(5)
    d, ell, trials = 64, 64, 50
    ests = []
    for i in range(trials):
        uni = rng.integers(1, 1 << 32, size=3000, dtype=np.uint64).astype(np.uint32)
        uni = np.unique(uni)[: 2 * d]
        a, b = uni[:d], uni[d:]
        seeds = rng.integers(0, 1 << 32, size=ell, dtype=np.uint64).astype(np.uint32)
        est = tow_estimate(jnp.array(a), jnp.array(b), jnp.array(seeds))
        ests.append(float(est))
    mean, var = float(np.mean(ests)), float(np.var(ests))
    exp_var = (2 * (2 * d) ** 2 - 2 * (2 * d)) / ell  # diff = 2d here
    assert abs(mean - 2 * d) < 6 * np.sqrt(exp_var / trials)
    assert var < 2.5 * exp_var


@pytest.mark.parametrize("n,t", [(63, 8), (127, 13), (255, 9)])
def test_sketch_groups_matches_core(n, t):
    code = BCHCode(n, t)
    rng = np.random.default_rng(n)
    bitmaps, expected = [], []
    for _ in range(9):
        pos = rng.choice(n, size=int(rng.integers(0, t + 1)), replace=False)
        bm = np.zeros(n, dtype=np.int32)
        bm[pos] = 1
        bitmaps.append(bm)
        expected.append(sketch_from_positions(code, pos))
    out = np.array(sketch_groups(jnp.array(np.stack(bitmaps)), code))
    np.testing.assert_array_equal(out, np.stack(expected))


@pytest.mark.parametrize("n,t", [(63, 8), (127, 13), (255, 9)])
def test_bch_decode_batched_matches_numpy(n, t):
    code = BCHCode(n, t)
    rng = np.random.default_rng(t)
    sketches = []
    for _ in range(32):
        d = int(rng.integers(0, t + 4))  # include overload rows
        pos = rng.choice(n, size=d, replace=False)
        sketches.append(sketch_from_positions(code, pos))
    sk = np.stack(sketches)
    ok_np, pos_np = batched_decode(code, sk)
    ok_j, pos_j, cnt_j = jax.device_get(bch_decode_batched(jnp.array(sk), n=n, t=t))
    np.testing.assert_array_equal(np.array(ok_j), ok_np)
    for i in range(len(sk)):
        got = set(int(p) for p in pos_j[i] if p >= 0)
        assert got == set(pos_np[i].tolist()), i


def test_encode_group_end_to_end():
    code = BCHCode(127, 9)
    rng = np.random.default_rng(1)
    elems = rng.integers(1, 1 << 32, size=500, dtype=np.uint64).astype(np.uint32)
    parity, xors, sketch = encode_group(jnp.array(elems), code, seed=3)
    p_ref, _, xors_ref = ref.bin_parity_xorsum_ref(elems, 127, 3)
    np.testing.assert_array_equal(np.array(parity), p_ref)
    np.testing.assert_array_equal(np.array(xors), xors_ref)
    exp_sketch = sketch_from_positions(code, np.nonzero(p_ref)[0])
    np.testing.assert_array_equal(np.array(sketch), exp_sketch)


def test_chien_matmul_finds_roots():
    code = BCHCode(127, 7)
    gf = code.field
    rng = np.random.default_rng(2)
    pos = rng.choice(127, size=5, replace=False)
    # Lambda(x) = prod (1 - alpha^p x) has roots alpha^{-p}
    lam = np.zeros(8, dtype=np.int64)
    lam[0] = 1
    for p in pos:
        nxt = lam.copy()
        nxt[1:] ^= gf.mul(lam[:-1], gf.pow_alpha(p))
        lam = nxt
    bits = gf.to_bits(lam).reshape(-1)
    ev = np.array(chien_eval_matmul(jnp.array(bits[None, :]), code))
    roots = np.nonzero(~ev[0].any(axis=1))[0]
    assert set(roots.tolist()) == set(pos.tolist())


def test_kernel_pipeline_vs_protocol_roundtrip():
    """Kernel encode on both sides -> XOR sketches -> JAX decode -> bins match."""
    code = BCHCode(255, 11)
    rng = np.random.default_rng(3)
    base = np.unique(rng.integers(1, 1 << 32, size=4000, dtype=np.uint64).astype(np.uint32))
    a, b = base, base[:-6]  # 6 distinct elements
    pa, xa, ska = encode_group(jnp.array(a), code, seed=11)
    pb, xb, skb = encode_group(jnp.array(b), code, seed=11)
    ok, pos, cnt = jax.device_get(
        bch_decode_batched((ska ^ skb)[None, :], n=255, t=11)
    )
    assert bool(ok[0])
    recovered = set()
    xa_np, xb_np = np.array(xa), np.array(xb)
    for p in pos[0][: int(cnt[0])]:
        s = int(xa_np[p] ^ xb_np[p])
        recovered.add(s)
    diff = set(int(x) for x in a) ^ set(int(x) for x in b)
    # all-singleton bins recover exactly; collisions (rare at n=255,d=6) tolerated
    assert len(recovered & diff) >= 4
