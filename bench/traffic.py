"""The one traffic generator.  A mix is a data file, ``bench/traffic/<mix>.json``:

* ``loop``: ``"open"`` (independent users: requests are due on a schedule,
  whatever the server does) or ``"closed"`` (``clients`` callers, each
  sending its next request when the previous reply is complete);
* ``rate_rps`` (open loop): mean arrival rate, Poisson;
* ``clients`` (closed loop) and ``requests_per_client``: the length of
  each client's sequence, cycled if the window outlasts it;
* ``tenant_zipf_s``: tenant popularity, share of tenant i proportional to
  (i+1)^-s over the configuration's instances (0 = uniform).  The open
  loop splits its requests so, the closed loop its clients;
* ``prompt_tokens`` / ``output_tokens``: lognormal ``median`` and
  ``sigma``, clipped to [``min``, ``max``];
* ``pattern_seed`` (open loop): the order of the lengths and gaps.

Every seed gets the same work: the same multiset of prompt lengths,
output lengths, tenants and inter-arrival gaps, taken at evenly spaced
quantiles of the distributions above, with token ids drawn from the seed.
In the open loop the lengths and arrival times keep the file's order
(``pattern_seed``) and the seed deals the tenants: which request lands on
a long decode block moves the TTFT tail of a one-minute window by a fifth
from order to order, far more than two runs of one order differ.  In the
closed loop each round (every client's r-th request) holds the same
multiset, dealt to the clients in a seeded order.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Req:
    tenant: int
    prompt: tuple[int, ...]
    max_new: int
    due: float = 0.0        # open loop: seconds after the window opens


@dataclasses.dataclass(frozen=True)
class Plan:
    loop: str
    requests: tuple[Req, ...] = ()                   # open loop, by due time
    clients: tuple[tuple[Req, ...], ...] = ()        # closed loop


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def split(total: int, shares) -> list[int]:
    """``total`` split in proportion to ``shares`` by largest remainder."""
    shares = np.asarray(shares, float)
    exact = total * shares / shares.sum()
    out = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - out), kind="stable")[: total - out.sum()]:
        out[i] += 1
    return out.tolist()


def tenant_shares(n: int, zipf_s: float) -> np.ndarray:
    return (np.arange(1, n + 1, dtype=float)) ** -float(zipf_s)


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the clipped lognormal."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(int)


def _requests(spec, n, tenants, vocab, rng, order) -> list[Req]:
    """``n`` requests, one per tenant given: lengths at the quantiles, each
    kind in its own order drawn from ``order``, token ids from ``rng``."""
    prompts = order.permutation(lognormal_quantiles(spec["prompt_tokens"], n))
    outs = order.permutation(lognormal_quantiles(spec["output_tokens"], n))
    return [Req(int(t), tuple(rng.integers(0, vocab, size=int(p)).tolist()),
                int(o))
            for t, p, o in zip(tenants, prompts, outs)]


def plan(spec: dict, *, instances: int, vocab: int, seconds: float,
         seed: int) -> Plan:
    """The requests of one run of ``seconds`` seconds under mix ``spec``."""
    rng = np.random.default_rng(seed)
    shares = tenant_shares(instances, spec.get("tenant_zipf_s", 0.0))
    if spec["loop"] == "open":
        n = max(1, round(spec["rate_rps"] * seconds))
        order = np.random.default_rng(spec["pattern_seed"])
        tenants = rng.permutation(
            np.repeat(np.arange(instances), split(n, shares)))
        # exponential gaps at evenly spaced quantiles, scaled so that the
        # n arrivals fill [0, seconds) at the mean rate
        gaps = order.permutation(
            [-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        due = seconds * (np.cumsum(gaps) - gaps) / gaps.sum()
        reqs = _requests(spec, n, tenants, vocab, rng, order)
        return Plan("open", requests=tuple(
            dataclasses.replace(r, due=float(d)) for r, d in zip(reqs, due)))
    if spec["loop"] == "closed":
        tenants = np.repeat(np.arange(instances),
                            split(spec["clients"], shares))
        rounds = [_requests(spec, len(tenants), tenants, vocab, rng, rng)
                  for _ in range(spec["requests_per_client"])]
        return Plan("closed", clients=tuple(zip(*rounds)))
    raise ValueError(f"unknown loop {spec['loop']!r}")
