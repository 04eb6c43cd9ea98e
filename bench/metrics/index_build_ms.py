"""Device time of the cohort step under its ``cohort.build_index`` scope
(the OPS index of each unique replica τ) per changeset, in ms: the union of
the intervals of the operations the scope table names so, over the number
of ``broker.process_changeset`` spans in the window."""
import program_trace


def read(run):
    return program_trace.phase_ms(program_trace.load(run),
                                  ("cohort.build_index",))
