"""Compile the served kernels and executors for a TPU v5e, with no chip.

The TPU compiler is installed alongside jaxlib and compiles for a described
``v5e:2x2`` topology whose devices are not attached.  Interpret mode cannot
see what Mosaic refuses (operand dtypes the MXU does not take, block shapes
off the (8, 128) tiling), so every kernel of the served path is compiled
here at real widths with ``interpret=False``, and each compiled program must
contain the kernel (``tpu_custom_call``).  Nothing runs: these tests say
nothing about results or times.

The topology is described only inside a fixture: loading the TPU library at
import time would make every test worker hold its lock.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.bch import bch_code
from repro.kernels.bin_xorsum import bin_parity_xorsum_units
from repro.kernels.ops import bch_decode_batched, sketch_groups, sketch_groups_range
from repro.kernels.tow_sketch import tow_sketch
from repro.kernels.tree_digest import tree_digest
from repro.recon import engine

# Real widths: the (255, 8) code a d = 1000 replica pair plans, 512 units
# (2 sessions x 200 groups, pow2-bucketed), 8192-key unit rows (10^6 keys
# over 200 groups), and a 2 x 10^6-key resident store.
N_BINS, T = 255, 8
UNITS, WIDTH = 512, 8192
STORE, ROWS = 2_000_128, 400
OVERLAY, FILTERS = 8, 1
# The hub's round 1 in the benchmark's outage cell: 32 streams at
# d = 10^4 plan the (511, 10) code with 2,000 units each, 64,000 units
# bucketed to 65,536, rows of about 500 keys bucketed to 1,024, and a
# resident store of 32 x 10^6 keys.
OUTAGE = {"units": 65_536, "n": 511, "t": 10, "width": 1024,
          "store": 32_000_128, "rows": 64_000}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else the compiler logs to /tmp

    def restore_log():
        if saved_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        restore_log()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of the persistent cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    restore_log()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **static):
    jitted = jax.jit(fn, static_argnames=tuple(static))
    return jitted.lower(*args, **static).compile().as_text()


def test_sketch_groups_compiles(one_chip):
    code = bch_code(N_BINS, T)
    bitmaps = _spec(one_chip, (UNITS, N_BINS), jnp.int32)
    text = _compiled_text(lambda b: sketch_groups(b, code, interpret=False), bitmaps)
    assert "tpu_custom_call" in text


def test_sketch_groups_range_compiles(one_chip):
    code = bch_code(N_BINS, 2 * T)
    bitmaps = _spec(one_chip, (UNITS, N_BINS), jnp.int32)
    text = _compiled_text(
        lambda b: sketch_groups_range(b, code, T, interpret=False), bitmaps
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_bins", [N_BINS, 511, 1023])
def test_bin_parity_xorsum_units_compiles(one_chip, n_bins):
    text = _compiled_text(
        lambda e, v, s: bin_parity_xorsum_units(e, v, s, n_bins=n_bins, interpret=False),
        _spec(one_chip, (2 * UNITS, WIDTH), jnp.uint32),
        _spec(one_chip, (2 * UNITS, WIDTH), jnp.int32),
        _spec(one_chip, (2 * UNITS,), jnp.uint32),
    )
    assert "tpu_custom_call" in text


def test_tree_digest_compiles(one_chip):
    text = _compiled_text(
        lambda e, v, s: tree_digest(e, v, s, ell=32, interpret=False),
        _spec(one_chip, (64, 4096), jnp.uint32),
        _spec(one_chip, (64, 4096), jnp.int32),
        _spec(one_chip, (32,), jnp.uint32),
    )
    assert "tpu_custom_call" in text


def test_tow_sketch_compiles(one_chip):
    text = _compiled_text(
        lambda e, s, v: tow_sketch(e, s, v, ell=128, interpret=False),
        _spec(one_chip, (1 << 20,), jnp.uint32),
        _spec(one_chip, (128,), jnp.uint32),
        _spec(one_chip, (1 << 20,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_bch_decode_batched_compiles(one_chip):
    _compiled_text(
        lambda sk: bch_decode_batched(sk, n=N_BINS, t=T),
        _spec(one_chip, (UNITS, T), jnp.int32),
    )


def _store(sharding, store=STORE, rows=ROWS):
    return [
        _spec(sharding, (store,), jnp.uint32),
        _spec(sharding, (rows,), jnp.int32),
        _spec(sharding, (rows,), jnp.int32),
    ]


def _round_arrays(sharding, units=UNITS):
    u = (units,)
    return [
        _spec(sharding, u, jnp.int32),                      # row_map
        _spec(sharding, u, jnp.int32),                      # unit_valid
        _spec(sharding, u, jnp.uint32),                     # seeds
        _spec(sharding, (units, OVERLAY), jnp.uint32),      # removed
        _spec(sharding, u, jnp.int32),                      # removed_cnt
        _spec(sharding, (units, OVERLAY), jnp.uint32),      # added
        _spec(sharding, u, jnp.int32),                      # added_cnt
        _spec(sharding, (units, FILTERS), jnp.uint32),      # fseeds
        _spec(sharding, (units, FILTERS), jnp.int32),       # fbins
        _spec(sharding, u, jnp.int32),                      # fcnt
    ]


@pytest.mark.parametrize(
    "executor", ["_execute_round", "_encode_side", "_execute_round_ext", "_encode_side_ext"]
)
def test_round_executor_compiles(one_chip, executor):
    both_sides = executor.startswith("_execute")
    stores = _store(one_chip) + (_store(one_chip) if both_sides else [])
    widths = (
        {"width_a": WIDTH, "width_b": WIDTH} if both_sides else {"width": WIDTH}
    )
    codes = {"t0": T, "t1": 2 * T} if executor.endswith("_ext") else {"t": T}
    text = _compiled_text(
        getattr(engine, executor),
        *stores, *_round_arrays(one_chip),
        n=N_BINS, interpret=False, **codes, **widths,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("executor", ["_encode_side", "_execute_round"])
def test_round_executor_fits_the_outage_cell(one_chip, executor):
    """Round 1 of the outage cell at its real sizes fits one v5e with room
    to spare: the binning output is lane-dense, (U, n) words with bins on
    lanes, so no (U, n, 33) bit-plane array padded to 128 lanes is made."""
    both_sides = executor == "_execute_round"
    store = _store(one_chip, OUTAGE["store"], OUTAGE["rows"])
    width = OUTAGE["width"]
    widths = {"width_a": width, "width_b": width} if both_sides else {"width": width}
    jitted = jax.jit(getattr(engine, executor),
                     static_argnames=("n", "t", "interpret", *widths))
    compiled = jitted.lower(
        *store, *(store if both_sides else []),
        *_round_arrays(one_chip, OUTAGE["units"]),
        n=OUTAGE["n"], t=OUTAGE["t"], interpret=False, **widths,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
