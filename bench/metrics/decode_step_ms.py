"""Executor layer: wall time of the decode-only steps (each ends in the
host's read of its tokens, which waits for the device), summed over the
window, over their count."""


def read(run):
    dec = [s.exec_s for s in run.window.steps if s.decode_only]
    return 1000.0 * sum(dec) / len(dec) if dec else None
