"""Device time of the broker's cohort-step executables per changeset, in
ms: the sum of their ``XLA Modules`` events in the window over the number of
changesets ingested. The trace names the executables that
``make_cohort_step`` builds ``jit_step(<hash>)`` and ``jit_step_delta(<hash>)``."""

COHORT_MODULES = ("jit_step(", "jit_step_delta(")


def read(run):
    t = run.trace
    if t is None or not t.count("bench.ingest"):
        return None
    s = t.module_seconds(lambda e: e.name.startswith(COHORT_MODULES))
    if s <= 0:
        return None
    return 1e3 * s / t.count("bench.ingest")
