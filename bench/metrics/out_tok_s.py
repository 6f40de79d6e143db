"""Output tokens emitted in the window over the window's length."""


def read(run):
    w = run.window
    return sum(len(sv.stamps) for sv in w.served) / w.close
