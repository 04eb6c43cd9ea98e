"""As ``cohort_step_ms``, in the saturated cells: device time of the cohort-step
executables (``jit_step``, ``jit_step_delta``) per changeset, in ms."""

COHORT_MODULES = ("jit_step(", "jit_step_delta(")


def read(run):
    t = run.trace
    if t is None or not t.count("bench.ingest"):
        return None
    s = t.module_seconds(lambda e: e.name.startswith(COHORT_MODULES))
    if s <= 0:
        return None
    return 1e3 * s / t.count("bench.ingest")
