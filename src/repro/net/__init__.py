"""Two-endpoint PBS reconciliation over real transports (DESIGN.md §9).

``AliceEndpoint`` and ``BobEndpoint`` split the in-process
``repro.recon.ReconcileServer`` into genuine peers that communicate *only*
via ``repro.wire``-encoded bytes over a ``Transport``: an in-memory duplex
for tests, a TCP loopback socket, or a simulated lossy/latent channel
behind the stop-and-wait ``ReliableTransport``.  Each endpoint keeps
driving the device-resident cohort pipeline for its own side — S
concurrent sessions still batch into fused kernel launches per round — and
both sides advance the *same* ``core.pbs`` round state machine, so
per-session results and measured wire ledgers are byte-identical to
``core.pbs.reconcile`` (asserted in tests/test_net_endpoints.py and
tests/test_recon_batch.py).

``HubEndpoint`` (DESIGN.md §10) is the one serving state machine, for N
concurrent peers on channel-multiplexed transports: all peers' sessions
fuse into one shared cohort pipeline, with per-peer round-barrier
deadlines so a straggler or mid-protocol disconnect fails only its own
peer.  ``BobEndpoint`` is that hub serving exactly one peer whose frames
carry no ``MSG_MUX`` envelope — the pair.

With ``continuous=True`` every endpoint also reconciles *divergent
replicas continuously* (DESIGN.md §11): ``advance_epoch`` stages the next
epoch's set mutations, ``run_epoch``/``serve_epoch``/``serve`` exchange the
``MSG_EPOCH`` d̂ handshake and delta-patch the device-resident stores in
place, so a long-lived peer pays O(churn) — not O(|set|) — per epoch.

``repro.net.resilience`` (DESIGN.md §13) hardens all of it against real
failure: ``FaultPlan``/``ChaosTransport`` script seeded loss bursts,
duplication, reordering, corruption, partitions, and crash-restart under
any of the transports; a crashed-and-restarted peer re-attaches through
the ``MSG_RESUME`` handshake (``AliceEndpoint.resume`` against
``HubEndpoint.resume_peer``) and continues from its last completed round
barrier with zero store rebuilds; ``classify_error`` types every failure
for ``PeerOutcome.error_kind``; and ``degrade=True`` endpoints escalate
decode-budget-exhausted sessions instead of failing them.
"""
from .endpoint import AliceEndpoint
from .hub import (
    BobEndpoint,
    HubEndpoint,
    PeerOutcome,
    run_hub,
    run_hub_epoch,
    run_pair,
    run_pair_epoch,
)
from .resilience import ChaosTransport, FaultPlan, PeerDeadline, classify_error
from .transport import (
    FrameStream,
    InMemoryDuplex,
    ReliableTransport,
    SimulatedChannel,
    SocketTransport,
    Transport,
    TransportError,
    TransportTimeout,
    tcp_loopback_pair,
)

__all__ = [
    "AliceEndpoint",
    "BobEndpoint",
    "ChaosTransport",
    "FaultPlan",
    "FrameStream",
    "HubEndpoint",
    "InMemoryDuplex",
    "PeerDeadline",
    "PeerOutcome",
    "ReliableTransport",
    "SimulatedChannel",
    "SocketTransport",
    "Transport",
    "TransportError",
    "TransportTimeout",
    "classify_error",
    "run_hub",
    "run_hub_epoch",
    "run_pair",
    "run_pair_epoch",
    "tcp_loopback_pair",
]
