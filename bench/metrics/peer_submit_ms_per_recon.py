"""Time the peers' set-up takes on the hub's thread (the program's spans
``endpoint.submit``: each peer's ``np.unique`` and session state, from
``repro.core.pbs.new_session_state``), summed over the window, per
reconciliation.  Read from a traced run only; a program without the span
reports nothing."""

SPANS = ("endpoint.submit",)


def read(run):
    if run.spans is None:
        return None
    durs = [dur for name, dur in run.spans if name in SPANS]
    if not durs:
        return None
    return sum(durs) * 1e3 / len(run.recons)
