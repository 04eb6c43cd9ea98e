"""Changesets fully delivered, over the time from the window's start to the
last one's delivery (the changeset in flight at the close is finished)."""


def read(run):
    if not run.n_delivered or run.window_s <= 0:
        return None
    return run.n_delivered / run.window_s
