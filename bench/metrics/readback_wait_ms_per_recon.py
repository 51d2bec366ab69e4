"""Time the hub's thread is blocked on the chip (the program's span
``device.readback``: each ``jax.device_get`` of a round's encode and
decode outputs), summed over the window, per reconciliation.  Read from a
traced run only; a program without the span reports nothing."""

SPANS = ("device.readback",)


def read(run):
    if run.spans is None:
        return None
    durs = [dur for name, dur in run.spans if name in SPANS]
    if not durs:
        return None
    return sum(durs) * 1e3 / len(run.recons)
