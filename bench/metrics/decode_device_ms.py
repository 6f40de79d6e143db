"""Executor, chip side: from the program's step records, the mean over the
window's decode-only steps of the time from the ``dispatch`` span's start
to the ``sync`` span's end (the step's enqueue, its run on the chip and the
read of its tokens)."""
from steprec import mean, window_records


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    return mean((r.t_synced - r.t_dispatch) / 1e6 for r in recs
                if r.decode_items and not r.prefill_tokens
                and r.t_dispatch is not None and r.t_synced is not None)
