"""Device-idle time inside the program's ``broker.evaluate`` spans
(statics, the bank pass and cohort dispatch, and the host's wait on each
cohort's overflow flag) per changeset, in ms."""
import program_trace


def read(run):
    return program_trace.idle_in_ms(program_trace.load(run),
                                    "broker.evaluate")
