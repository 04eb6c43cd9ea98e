"""Share of the cohort-step executables' device time (their ``XLA Modules``
intervals) that falls under some ``cohort.*`` scope, in %: how much of
``cohort_step_ms`` the phase metrics can split."""
import program_trace


def read(run):
    return program_trace.scoped_share(program_trace.load(run))
