"""Process start → the window opens: weights, executor, warm-up by key."""


def read(run):
    return run.setup_s
