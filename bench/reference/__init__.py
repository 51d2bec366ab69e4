"""The plain reference both configurations are held to: PBS in numpy.

A copy of ``repro.core`` (``pbs``, ``bch``, ``gf2m``, ``hashing``,
``markov``, ``tow``) as it stood when the benchmark was defined, so that no
later change to the program can move the yardstick.  It imports nothing of
the program and never imports JAX, so the comparison can run in worker
processes beside a process that holds the chip.  The one addition is the
``work`` argument of ``pbs.reconcile``, which records the keys each round
bins.
"""
