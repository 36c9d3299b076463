"""The traffic generator, on the CPU."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 17


def _plan(mix, seed=SEED, seconds=30.0):
    return traffic.plan(traffic.load(mix), instances=4, vocab=1000,
                        seconds=seconds, seed=seed)


@pytest.mark.parametrize("mix", ["chat-1.2rps", "batch-skew"])
def test_same_seed_same_requests(mix):
    assert _plan(mix) == _plan(mix)
    assert _plan(mix) != _plan(mix, seed=SEED + 1)


def _requests(p):
    return list(p.requests) + [r for c in p.clients for r in c]


@pytest.mark.parametrize("mix", ["chat-1.2rps", "batch-skew"])
def test_lengths_within_clips(mix):
    spec = traffic.load(mix)
    for r in _requests(_plan(mix)):
        lo, hi = spec["prompt_tokens"]["min"], spec["prompt_tokens"]["max"]
        assert lo <= len(r.prompt) <= hi
        lo, hi = spec["output_tokens"]["min"], spec["output_tokens"]["max"]
        assert lo <= r.max_new <= hi
        assert all(0 <= t < 1000 for t in r.prompt)


@pytest.mark.parametrize("mix", ["chat-1.2rps", "batch-skew"])
def test_every_seed_gets_the_same_work(mix):
    a, b = _requests(_plan(mix, seed=1)), _requests(_plan(mix, seed=SEED))
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert sorted(r.tenant for r in a) == sorted(r.tenant for r in b)


def test_open_loop_keeps_the_files_order():
    a, b = _plan("chat-1.2rps", seed=1), _plan("chat-1.2rps", seed=SEED)
    assert [(len(r.prompt), r.max_new, r.due) for r in a.requests] == \
        [(len(r.prompt), r.max_new, r.due) for r in b.requests]
    assert [r.tenant for r in a.requests] != [r.tenant for r in b.requests]


def test_batch_skew_client_split():
    p = _plan("batch-skew")
    counts = np.bincount([c[0].tenant for c in p.clients], minlength=4)
    assert counts.tolist() == [16, 8, 5, 3]
    assert all(len({r.tenant for r in c}) == 1 for c in p.clients)


def test_open_loop_mean_rate_and_poisson_gaps():
    spec = traffic.load("chat-1.2rps")
    seconds = 200.0
    p = _plan("chat-1.2rps", seconds=seconds)
    due = np.array([r.due for r in p.requests])
    assert len(due) == round(spec["rate_rps"] * seconds)
    assert np.all(np.diff(due) > 0) and due[0] == 0 and due[-1] < seconds
    gaps = np.diff(due)
    # exponential gaps: the standard deviation is about the mean
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)
    tenants = np.bincount([r.tenant for r in p.requests], minlength=4)
    assert tenants.max() - tenants.min() <= 1


def test_lognormal_median():
    spec = {"median": 256, "sigma": 0.8, "min": 1, "max": 10**6}
    assert np.median(traffic.lognormal_quantiles(spec, 1001)) == 256


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen1.5-0.5b.m4.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
