"""Device time of the cohort step under its ``cohort.eval_removed`` and
``cohort.eval_added`` scopes (Defs 11-15 over D and over I = A ∪ ρ: the
probes of τ and the joins) per changeset, in ms: the union of the intervals
of those operations, over the number of ``broker.process_changeset`` spans
in the window."""
import program_trace


def read(run):
    return program_trace.phase_ms(program_trace.load(run),
                                  ("cohort.eval_removed", "cohort.eval_added"))
