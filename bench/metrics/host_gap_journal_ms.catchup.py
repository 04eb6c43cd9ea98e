"""Device-idle time inside the program's ``journal.append`` spans (encode,
write and fsync of the ingest and fire records) per changeset, in ms."""
import program_trace


def read(run):
    return program_trace.idle_in_ms(program_trace.load(run), "journal.append")
