"""Every seed serves the same work, in the same order."""
import pytest

from traffic import lognormal_params, make_jobs

OPEN = {"loop": "open", "base_seed": 0,
        "lengths": {"prompt_avg": 1604, "prompt_p90": 3561, "output_avg": 114,
                    "output_p90": 392, "max_total": 4096},
        "slo": {"ttft_s": 2.0, "tpot_s": 0.05},
        "arrivals": {"rate": 1.5, "burst_factor": 1.8, "burst_frac": 0.35,
                     "burst_sojourn_s": 1.5, "calm_sojourn_s": 4.0}}
CLOSED = {"loop": "closed", "base_seed": 0, "clients": 4,
          "jobs_per_client": 3, "lengths": OPEN["lengths"], "slo": OPEN["slo"]}


def test_lognormal_fit_hits_mean_and_p90():
    import math

    mu, sigma = lognormal_params(1604, 3561)
    assert math.exp(mu + sigma ** 2 / 2) == pytest.approx(1604)
    assert math.exp(mu + 1.281551565545 * sigma) == pytest.approx(3561)


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_seeds_share_the_work(mix):
    a = make_jobs(mix, 2**31 + 12345, 51.0, 32000)
    b = make_jobs(mix, 7, 51.0, 32000)
    assert [(len(j.prompt), j.out_len, j.due, j.client) for j in a] == \
        [(len(j.prompt), j.out_len, j.due, j.client) for j in b]
    assert [j.prompt for j in a] != [j.prompt for j in b]
    assert all(len(j.prompt) + j.out_len <= 4096 for j in a)


def test_same_seed_same_jobs():
    a = make_jobs(OPEN, 99, 51.0, 32000)
    b = make_jobs(OPEN, 99, 51.0, 32000)
    assert [(j.due, j.prompt, j.out_len) for j in a] == \
        [(j.due, j.prompt, j.out_len) for j in b]


def test_open_loop_rate():
    jobs = make_jobs(OPEN, 1, 2000.0, 100)
    assert len(jobs) / 2000.0 == pytest.approx(1.5, rel=0.15)
