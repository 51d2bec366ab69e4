"""Device time of the BCH decode (the ops of the ``bch_decode_batched``
programs in the traced window), summed, per reconciliation."""
import re

# the decode's programs as the device trace names them: <program>/<op>
PROGRAM = re.compile(r"^[^/]*bch_decode_batched[^/]*/")


def read(run):
    if run.device_trace is None:
        return None
    secs = [s for name, s in run.device_trace["op_s"].items() if PROGRAM.search(name)]
    if not secs:
        return None
    return sum(secs) * 1e3 / len(run.recons)
