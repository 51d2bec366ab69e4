"""Share of the traced window in which no operation ran on the device:
one minus the union of the device op intervals over the window."""


def read(run):
    if run.device_trace is None or not run.device_trace["op_s"]:
        return None
    t = run.device_trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
