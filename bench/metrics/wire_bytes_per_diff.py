"""Every byte on the wire in both directions over the window (framed
protocol, MUX and EPOCH envelopes, verify and estimator frames), as the
hub's transports counted it, per element of A xor B reconciled."""


def read(run):
    diffs = sum(len(r.served[1]) for r in run.recons if r.served is not None)
    wire = sum(s.wire_bytes for s in run.steps)
    return wire / diffs if diffs else None
