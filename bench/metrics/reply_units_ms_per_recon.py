"""Host time the hub spends building each decoded unit's reply (the
program's span ``decode.reply_units`` in ``decode_side_b_round``) on the
hub's thread, summed over the window, per reconciliation.  Read from a
traced run only; a program without the span reports nothing."""

SPANS = ("decode.reply_units",)


def read(run):
    if run.spans is None:
        return None
    durs = [dur for name, dur in run.spans if name in SPANS]
    if not durs:
        return None
    return sum(durs) * 1e3 / len(run.recons)
