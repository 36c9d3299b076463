"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them, is run through the plain reference (``bench/reference``)
over each prompt and its served tokens, on weights the benchmark makes
again from the seed.  The number compared is the widest gap, over every
served token of the sample, by which the served token's reference logit
lies below the reference's best logit at that position.  Served tokens
are greedy, so a sound server reads only rounding here; a token altered
where it is produced, a wrong instance's weights, a wrong cache row or a
lower precision reads more.
"""
from __future__ import annotations

import importlib

import numpy as np

# requests compared in each run: the longest the window finished, and the
# rest drawn from the seed (some hundreds of served tokens in all)
SAMPLE_REQUESTS = 8


def sample(records, t_close: float, seed: int, n: int = SAMPLE_REQUESTS):
    """Up to ``n`` requests finished in the window: the longest (prompt and
    served tokens) and the rest drawn from the seed."""
    done = [r for r in records
            if r.status == "ok" and r.times and r.times[-1] <= t_close]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(arch: str, model, grid, samples, max_context: int,
                control: bool = False) -> dict:
    """Widest served-token gap over the sample (and the control's, where
    ``control``), with the counts of requests and tokens compared."""
    ref = importlib.import_module(f"bench.reference.{arch}")
    served, low, tokens = -np.inf, -np.inf, 0
    for r in samples:
        if r.engine_tokens != r.tokens:
            raise RuntimeError("client stream and engine result differ for "
                               f"a request of tenant {r.req.tenant}")
        seq = list(r.req.prompt) + r.tokens[:-1]
        toks = np.zeros(max_context, np.int32)
        toks[: len(seq)] = seq
        first = len(r.req.prompt) - 1
        targets = np.zeros(max_context, np.int32)
        targets[first: first + len(r.tokens)] = r.tokens
        mask = np.zeros(max_context, bool)
        mask[first: first + len(r.tokens)] = True
        g, c = ref.gaps(model, control, grid, np.int32(r.req.tenant), toks,
                        targets, mask)
        served, low = max(served, float(g)), max(low, float(c))
        tokens += len(r.tokens)
    return {"served_gap": served, "control_gap": low if control else None,
            "requests": len(samples), "tokens": tokens}


def passes(gap: float, requests: int, limit: float) -> bool:
    """The decision of ``correct``: some requests were compared, and their
    widest served-token gap lies within the limit (a NaN gap fails)."""
    return requests > 0 and gap <= limit
