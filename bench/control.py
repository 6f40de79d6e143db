"""The control of the served-token check, on the chip at the cell's size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \
        [--dump <dir>]

For each seed, in one process, the cell is served for ``--seconds`` as a
run serves it, and the run's own sample of finished requests is compared
with the float32 reference twice: once for the tokens the program served,
and once for the tokens a bfloat16 copy of the reference puts first at the
same positions (the control: the nearest precision below the float32 the
configuration states). Both readings go through the run's own limits
(``run.judge``); a sound check passes the program and fails the control.
One JSON line per seed (each seed holds a whole model: on one chip, give
one seed per process). With ``--dump``, the gap at every served position
of every finished request, for the program and for the control, goes to
``<dir>/gaps-<seed>.npz``. The benchmark's runs never call this script.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

import run as bench_run
from drive import build_engine, serve_window
from traffic import make_jobs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default="")
    args = ap.parse_args()
    root = bench_run.BENCH.parent
    sys.path.insert(0, str(root / "src"))
    sys.path.append(str(root))
    from lm import Reference

    for seed in (int(x) for x in args.seeds.split(",")):
        c = bench_run.prepare(root, args.workload, seed)
        eng = build_engine(c.conf, c.shape, c.ex, c.mix)
        jobs = make_jobs(c.mix, seed, args.seconds, c.shape.vocab)
        win = serve_window(eng, c.ex, jobs, c.mix, args.seconds,
                           c.shape.window, c.counter)
        bench_run.report_window(win)
        del eng
        c.ex = None
        gc.collect()
        limits = c.conf["check"]["limits"]
        fin = [sv for sv in win.served if sv.finished]
        short = sum(len(sv.tokens) != sv.out_len for sv in fin)
        pick = {sv.rid for sv in bench_run.sample_finished(win, seed)}
        t0 = time.perf_counter()
        ref = Reference(c.shape, c.weights)
        out = {"seed": seed, "finished": len(fin), "sample": len(pick)}
        dump = {"rid": np.array([sv.rid for sv in fin]),
                "n": np.array([len(sv.tokens) for sv in fin])}
        for name, read in (("program", ref.served_gaps),
                           ("control", ref.control_gaps)):
            per = [read(sv.prompt, sv.tokens) for sv in fin]
            dump[name] = np.concatenate(per)
            gaps = np.concatenate([g for g, sv in zip(per, fin)
                                   if sv.rid in pick])
            nums = dict(bench_run.gap_numbers(gaps), short_streams=short)
            out[name] = dict(nums, tokens=int(gaps.size),
                             correct=bench_run.judge(nums, limits),
                             seconds=time.perf_counter() - t0)
        print("control " + json.dumps(out), flush=True)
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            np.savez(Path(args.dump) / f"gaps-{seed}.npz", **dump)
        del c, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
