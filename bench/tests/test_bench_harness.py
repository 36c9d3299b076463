"""The harness end to end at a tiny size on the CPU, the look for a chip
skipped: a sound run comes out correct, a run with the timed path broken
underneath does not, and the lower-precision control reads above the
limit on the same requests."""
import json
from pathlib import Path

import jax
import pytest

from bench import check, peaks, run

DATA = Path(__file__).resolve().parent / "data"
E2E = {"ttft_p90_ms": "ms", "itl_p95_ms": "ms", "tokens_per_s": "tokens/s",
       "setup_s": "s"}
LAYERS = ["queue_wait_p90_ms", "grid_occupancy", "prefill_us_per_token",
          "decode_step_ms", "decode_roofline", "decode_mfu", "mfu",
          "device_idle_share"]
SEED = 2**35 + 11


@pytest.fixture(scope="module")
def tiny():
    return (json.loads((DATA / "tiny.json").read_text()),
            json.loads((DATA / "tiny_traffic.json").read_text()))


def _run(tiny, *, trace=False, logdir=None, seed=SEED):
    cfg, spec = tiny
    return run.run_cell(
        "tiny", cfg, spec, seed=seed, seconds=2.0, trace=trace,
        devices=jax.devices()[:1], e2e=E2E,
        per_layer={k: "%" for k in LAYERS}, t_start=0.0, logdir=logdir)


def test_sound_run_is_correct(tiny):
    res = _run(tiny)
    assert res["correct"] is True
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == set(E2E)
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] <= gap["limit"]
    json.dumps(res)


def test_traced_run_reads_layers(tiny, tmp_path, monkeypatch):
    # the CPU has no published peaks: give it some for this test only
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.Peaks(1e12, 1e11))
    res = _run(tiny, trace=True, logdir=str(tmp_path / "trace"))
    assert res["correct"] is True
    m = res["metrics"]
    # the CPU trace has no device planes: device metrics are left out,
    # never reported as 0
    assert {"queue_wait_p90_ms", "grid_occupancy", "mfu"} <= set(m)
    assert "decode_roofline" not in m and "decode_step_ms" not in m
    assert 0 < m["grid_occupancy"]["value"] <= 100
    assert res["device"]["window_s"] > 0
    assert not (tmp_path / "trace").exists()


def test_altered_token_is_not_correct(tiny, monkeypatch):
    from repro.serving.engine import MultiModelServer

    vocab = tiny[0]["vocab_size"]
    make = MultiModelServer._make_block

    def broken(self, k):
        block = make(self, k)

        def call(*args):
            toks, emitted, oks, cache, key = block(*args)
            return (toks + 1) % vocab, emitted, oks, cache, key
        return call

    monkeypatch.setattr(MultiModelServer, "_make_block", broken)
    res = _run(tiny)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_reads_above_the_limit(tiny):
    # the control's reading goes through the decision that sets ``correct``
    # in a run, on the same requests as the program's, and fails it
    cfg, spec = tiny
    r = run.serve_window("tiny", cfg, spec, seed=SEED + 1, seconds=2.0,
                         trace=False, devices=jax.devices()[:1], t_start=0.0)
    gaps = run.reference_check(cfg, r, SEED + 1, control=True)
    limit = cfg["check"]["served_logit_gap"]
    assert gaps["tokens"] > 0
    assert check.passes(gaps["served_gap"], gaps["requests"], limit) is True
    assert check.passes(gaps["control_gap"], gaps["requests"], limit) is False


@pytest.mark.parametrize("gap, requests, expected", [
    (0.05, 8, True), (0.0, 1, True), (0.0501, 8, False),
    (float("nan"), 8, False), (0.0, 0, False)])
def test_passes_decides_correct(gap, requests, expected):
    # within the limit and with some requests compared; a reading that is
    # no number, or no request compared, is not correct
    assert check.passes(gap, requests, 0.05) is expected
