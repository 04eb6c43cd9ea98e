"""Device-idle time inside the harness's spans around each
``process_changeset`` call (and the ``flush`` at the window's close), per
changeset, in ms: the broker's host path (ingest,
policy loop, fire dispatch, commit, fanout, journal append) while the chip
waits for it."""


def read(run):
    t = run.trace
    if t is None or not t.count("bench.ingest"):
        return None
    idle = t.idle_inside(("bench.ingest", "bench.flush"))
    return 1e3 * idle / t.count("bench.ingest")
