"""Time the hub spends in its round barriers (the program's spans
``hub.collect_sketches`` and ``hub.collect_outcomes``, summed over the
window) per reconciliation.  Read from a traced run only."""

SPANS = ("hub.collect_sketches", "hub.collect_outcomes")


def read(run):
    if run.spans is None:
        return None
    wait_s = sum(dur for name, dur in run.spans if name in SPANS)
    return wait_s * 1e3 / len(run.recons)
