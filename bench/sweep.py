"""Find a cell's knee once, by a sweep of fixed offered rates in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.5,1,1.5 [--steps-out <file>]

The cell's model is built and warmed once, from the cell's step list. Then
each rate of ``--rates`` is served for ``--seconds`` on the mix's arrival
process at that rate, and one line per rate gives
what the knee is read from: the waiting queue (due, no first token) at
mid-window and at close, and the latency tails. The knee is the highest
rate whose queue at close is no larger than at mid-window. Every compile
key reached is written to ``--keys-out``. The benchmark's runs never call
this script.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

import run as bench_run
from drive import build_engine, read_shapes, save_shapes, serve_window
from stats import percentile, tpots, ttfts
from traffic import make_jobs


def serve_rate(c, seed: int, rate: float, seconds: float):
    mix = copy.deepcopy(c.mix)
    mix["arrivals"]["rate"] = rate
    eng = build_engine(c.conf, c.shape, c.ex, mix)
    jobs = make_jobs(mix, seed, seconds, c.shape.vocab)
    win = serve_window(eng, c.ex, jobs, mix, seconds, c.shape.window,
                       c.counter)
    for rid in list(eng.active):          # hand the pages back
        c.ex.release(rid)
    return win


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--steps-out", default="")
    args = ap.parse_args()
    root = bench_run.BENCH.parent
    sys.path.insert(0, str(root / "src"))
    sys.path.append(str(root))
    c = bench_run.prepare(root, args.workload, args.seed)
    rows, shapes = [], set(read_shapes(c.local_shapes))
    for r in (float(x) for x in args.rates.split(",")):
        win = serve_rate(c, args.seed, r, args.seconds)
        tt, tp = ttfts(win.served, win.close), tpots(win.served, win.close)
        row = {"rate": r, "due": len(win.served),
               "finished": sum(sv.finished for sv in win.served),
               "out_tok_s": sum(len(sv.stamps) for sv in win.served) / win.close,
               "waiting_mid": win.waiting_mid,
               "waiting_close": win.waiting_close,
               "ttft_p50_ms": 1000 * percentile(tt, 50),
               "ttft_p90_ms": 1000 * percentile(tt, 90),
               "tpot_p90_ms": 1000 * percentile(tp, 90) if tp else None,
               "window_programs": win.programs_in_window,
               "new_keys": len(win.new_shapes)}
        rows.append(row)
        shapes |= set(win.new_shapes)
        print("sweep " + json.dumps(row), flush=True)
    print(f"compile keys reached: {len(c.ex.compile_keys)}", flush=True)
    if args.steps_out:
        save_shapes(Path(args.steps_out), shapes)
    save_shapes(c.local_shapes, shapes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
