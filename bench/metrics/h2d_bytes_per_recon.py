"""Host-to-device bytes the hub uploaded (its ``h2d_bytes`` counter: store
builds, delta patches and round overlays) per reconciliation."""


def read(run):
    return sum(s.hub_h2d_bytes for s in run.steps) / len(run.recons)
