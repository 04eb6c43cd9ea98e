"""Device time of the cohort step under its ``cohort.combine`` scope (the
new replica τ′, ρ′ and Υ, Defs 16-18) per changeset, in ms: the union of the
intervals of those operations, over the number of
``broker.process_changeset`` spans in the window."""
import program_trace


def read(run):
    return program_trace.phase_ms(program_trace.load(run),
                                  ("cohort.combine",))
