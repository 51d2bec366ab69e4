"""Process start to the first timed operation: data generation, program
import, compilation or cache loads, and the warm-up steps (host clock)."""


def read(run):
    return run.setup_s
