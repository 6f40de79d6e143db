"""The one traffic generator: every mix is a data file under ``traffic/``
that this module reads.

A mix fixes, in its file:

- ``lengths``: a lognormal prompt and output length, each fitted in closed
  form to its mean and 90th percentile, and ``max_total``, the cap on
  prompt + output (the served context);
- ``slo``: the TTFT and TPOT limits every request carries;
- ``loop``: ``open`` (requests due on a schedule, whether or not earlier
  ones finished) or ``closed`` (``clients`` callers, each sending its next
  request when the previous one finishes, with no think time);
- for an open loop, ``arrivals``: a two-state Markov-modulated Poisson
  process (calm and burst) at a mean ``rate`` in requests per second.

The schedule (arrival times, and the lengths in their order) is drawn from
the mix's ``base_seed``, so every run seed serves the same work in the same
order and the run's steps take the same shapes (the compile keys a cell
warms are then a fixed list). The run seed draws the prompt token ids. The
length fit and the MMPP follow the paper's Table 2 profiles (FairBatching,
arXiv:2510.14392).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Job:
    """One request as the generator makes it. ``due`` is seconds after the
    window opens (open loop) or None (closed loop: due when sent)."""
    rid: int
    prompt: list
    out_len: int
    due: float | None = None
    client: int | None = None


def lognormal_params(avg: float, p90: float) -> tuple[float, float]:
    """mu, sigma with E[X] = avg and P90[X] = p90 (z90 = 1.2816)."""
    z = 1.281551565545
    disc = z * z - 2.0 * math.log(p90 / avg)
    sigma = z - math.sqrt(disc) if disc > 0 else z
    return math.log(avg) - sigma * sigma / 2.0, sigma


def draw_lengths(rng, lengths: dict, n: int) -> list[tuple[int, int]]:
    """n (prompt, output) pairs, capped so prompt + output <= max_total."""
    mu_p, sg_p = lognormal_params(lengths["prompt_avg"], lengths["prompt_p90"])
    mu_o, sg_o = lognormal_params(lengths["output_avg"], lengths["output_p90"])
    cap = int(lengths["max_total"])
    out = []
    for _ in range(n):
        p = max(int(lengths.get("min_prompt", 4)), int(rng.lognormal(mu_p, sg_p)))
        o = max(2, int(rng.lognormal(mu_o, sg_o)))
        p = min(p, cap - 2)
        o = min(o, cap - p)
        out.append((p, o))
    return out


def mmpp_arrivals(rng, arrivals: dict, duration: float) -> list[float]:
    """Two-state MMPP arrival times in [0, duration): the burst state runs
    at ``burst_factor`` x the mean rate for ``burst_frac`` of the time."""
    rate = float(arrivals["rate"])
    bf, frac = float(arrivals["burst_factor"]), float(arrivals["burst_frac"])
    rate_burst = bf * rate
    rate_calm = max((1 - frac * bf) / (1 - frac), 0.05) * rate
    sojourn = {True: float(arrivals["burst_sojourn_s"]),
               False: float(arrivals["calm_sojourn_s"])}
    times, t, burst = [], 0.0, False
    state_end = rng.exponential(sojourn[burst])
    while True:
        dt = rng.exponential(1.0 / (rate_burst if burst else rate_calm))
        if t + dt > state_end:
            t, burst = state_end, not burst
            state_end = t + rng.exponential(sojourn[burst])
            continue
        t += dt
        if t >= duration:
            return times
        times.append(t)


def make_jobs(mix: dict, seed: int, duration: float, vocab: int) -> list[Job]:
    """The jobs of one run: open-loop jobs carry their due time, closed-loop
    jobs their client (each client's jobs in sending order)."""
    base = np.random.default_rng(int(mix["base_seed"]))
    run = np.random.default_rng(int(seed))
    if mix["loop"] == "open":
        dues = mmpp_arrivals(base, mix["arrivals"], duration)
        sizes = draw_lengths(base, mix["lengths"], len(dues))
        return [Job(i, run.integers(0, vocab, p).tolist(), o, due=due)
                for i, (due, (p, o)) in enumerate(zip(dues, sizes))]
    if mix["loop"] == "closed":
        clients, per = int(mix["clients"]), int(mix["jobs_per_client"])
        sizes = draw_lengths(base, mix["lengths"], clients * per)
        return [Job(i, run.integers(0, vocab, p).tolist(), o,
                    client=i % clients)
                for i, (p, o) in enumerate(sizes)]
    raise ValueError(f"unknown loop {mix['loop']!r}")
