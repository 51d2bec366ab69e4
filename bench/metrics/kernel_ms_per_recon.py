"""Device time of every device op in the traced window, summed, per
reconciliation."""


def read(run):
    if run.device_trace is None or not run.device_trace["op_s"]:
        return None
    return sum(run.device_trace["op_s"].values()) * 1e3 / len(run.recons)
