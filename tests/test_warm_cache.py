"""Warm-compilation guarantees: shape buckets hold, retraces hit zero.

PR 6's executor contract (DESIGN.md §12): every device entry point —
round execute/encode, batched BCH decode, phase-0 ToW — runs at
``pow2_bucket`` shape signatures, so after a warmup pass over a workload's
buckets, later runs (and later continuous-sync epochs) trigger **zero**
jit recompilations.  ``stats["retraces"]`` counts actual traced executions
of the jitted bodies, so these tests fail if anyone reintroduces an
unbucketed shape into the hot path.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.pbs import PBSConfig
from repro.core.simdata import make_pair
from repro.kernels import platform
from repro.net import AliceEndpoint, HubEndpoint, InMemoryDuplex, run_hub, run_hub_epoch
from repro.recon import ReconcileServer


def _submit_grid(server, *, seed0=0):
    for i, d in enumerate((5, 50, 500)):
        a, b = make_pair({5: 1500, 50: 4000, 500: 8000}[d], d,
                         np.random.default_rng(d))
        server.submit(a, b, cfg=PBSConfig(seed=seed0 + i), d_known=d)
    # one estimator session so the warm contract covers phase 0 too
    a, b = make_pair(6000, 80, np.random.default_rng(2))
    server.submit(a, b, cfg=PBSConfig(seed=seed0 + 8), d_known=None)


def test_second_server_run_retraces_zero():
    """A fresh server over the same shape buckets must be fully warm: its
    run reports ``retraces == 0`` (process jit caches persist; a cold
    process warms on the first run and the persistent compilation cache
    carries signatures across processes)."""
    warm_up = ReconcileServer()
    _submit_grid(warm_up, seed0=0)
    warm_up.run()
    assert warm_up.stats["retraces"] >= 0  # counter wired (cold iff first)

    server = ReconcileServer()
    _submit_grid(server, seed0=0)
    results = server.run()
    assert all(r.success for r in results.values())
    assert server.stats["retraces"] == 0, server.stats


def test_hub_epoch_soak_retraces_zero_after_warmup():
    """The ISSUE 6 acceptance soak: a 4-peer continuous-sync hub across 3
    churn epochs — epoch 1 may still warm delta-path signatures, epochs 2
    and 3 must report ``retraces == 0`` in the hub stats."""
    peers, d = 4, 20
    rng = np.random.default_rng(77)
    hub = HubEndpoint(recv_deadline=30.0, continuous=True)
    alices = {}
    for p in range(peers):
        a, b = make_pair(700, d, np.random.default_rng(77 + 101 * p))
        dk = None if p == 3 else d     # one estimator peer: warm ToW too
        cfg = PBSConfig(seed=77 + p, n_override=127, t_override=7,
                        g_override=4)
        ta, tb = InMemoryDuplex.pair()
        ch = hub.add_peer(tb, label=f"peer{p}")
        hub.submit(ch, b, cfg=cfg, d_known=dk)
        ep = AliceEndpoint(ta, channel=ch, continuous=True)
        ep.submit(a, cfg=cfg, d_known=dk)
        alices[ch] = ep

    outcomes, _, errors = run_hub(hub, alices)
    assert not errors and all(o.ok for o in outcomes.values())
    assert "retraces" in hub.stats

    retraces = []
    for _ in range(1, 4):
        hub_muts, alice_muts = {}, {}
        for ch, ep in alices.items():
            b_cur = hub._peers[ch].sessions[0].state.b
            hub_muts[ch] = {0: (
                rng.integers(1, 1 << 32, size=8, dtype=np.uint64).astype(np.uint32),
                rng.permutation(b_cur)[:8],
            )}
            a_cur = ep.sessions[0].state.a
            alice_muts[ch] = {0: (
                rng.integers(1, 1 << 32, size=2, dtype=np.uint64).astype(np.uint32),
                rng.permutation(a_cur)[:2],
            )}
        hub.advance_epoch(hub_muts)
        for ch, ep in alices.items():
            ep.advance_epoch(alice_muts[ch])
        outcomes, _, errors = run_hub_epoch(hub, alices)
        assert not errors and all(o.ok for o in outcomes.values())
        retraces.append(hub.stats["retraces"])

    # epoch 1 is warmup; from epoch 2 on, every kernel signature must
    # already be compiled — cross-round AND cross-epoch
    assert retraces[1:] == [0, 0], retraces


_CACHE_PROBE = """
import os, jax, jax.numpy as jnp
from repro.kernels.platform import enable_persistent_cache
path = enable_persistent_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
print(len(os.listdir(path)))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_persistent_cache_placement(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` is the cache when set; otherwise the
    fixed checkout directory is.  Run in a fresh interpreter: JAX fixes its
    cache at a process's first compilation."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(Path(platform.__file__).parents[2]), env.get("PYTHONPATH")) if p
    )
    expect = str(tmp_path / "jax-cache") if from_env else platform.DEFAULT_CACHE_DIR
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = expect
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, check=True,
        capture_output=True, text=True, timeout=120,
    ).stdout.split()
    assert out[0] == out[1] == expect
    assert int(out[2]) >= 1          # the compiled program was written there
    if not from_env:                 # a fixed, git-ignored checkout path
        checkout = Path(platform.__file__).parents[3]
        assert Path(expect) == checkout / ".jax_cache"
        assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
