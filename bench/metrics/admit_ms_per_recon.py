"""Time the hub spends taking its side of each session in (the program's
spans ``hub.submit`` and ``hub.admit``: ``np.unique`` of B, then the
session states built at admission), summed over the window, per
reconciliation.  Read from a traced run only; a program without the spans
reports nothing."""

SPANS = ("hub.submit", "hub.admit")


def read(run):
    if run.spans is None:
        return None
    durs = [dur for name, dur in run.spans if name in SPANS]
    if not durs:
        return None
    return sum(durs) * 1e3 / len(run.recons)
