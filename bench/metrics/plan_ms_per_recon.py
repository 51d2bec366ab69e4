"""Time the hub spends planning its rounds (the program's span
``hub.plan_round``: cohort store builds and the per-round overlays),
summed over the window, per reconciliation.  Read from a traced run only;
a program without the span reports nothing."""

SPANS = ("hub.plan_round",)


def read(run):
    if run.spans is None:
        return None
    durs = [dur for name, dur in run.spans if name in SPANS]
    if not durs:
        return None
    return sum(durs) * 1e3 / len(run.recons)
