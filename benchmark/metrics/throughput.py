"""Queries completed in the window over the time from its start to the last
completion, in queries/s."""


def read(run):
    done = run.attempted - run.failed
    return done / run.window_s if done and run.window_s else None
