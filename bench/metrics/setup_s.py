"""Process start to window start: source, subscribe, warm-up (and, in a
checkout's first run, compilation)."""


def read(run):
    return run.setup_s
