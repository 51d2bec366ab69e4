"""Mean PBS rounds of the reconciliations that returned a result (the
program's result counters)."""


def read(run):
    rounds = [r.served[2] for r in run.recons if r.served is not None]
    return sum(rounds) / len(rounds) if rounds else None
