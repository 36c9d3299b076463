"""The reductions of the program's step spans and request stamps
(``bench/phases.py``): on synthetic records, and end to end on a traced
window of the tiny configuration on the CPU."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import peaks
from bench import phases as ph
from bench.drive import Record

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def synthetic():
    # device-idle gaps inside a step's prefill, under a frontend span,
    # inside a step's unroll, and between two steps under no span
    return {
        "devices": 1,
        "programs": [["decode_block", 90, 20, 0],      # from before the window
                     ["prefill_chunk", 110, 20, 0],
                     ["decode_block", 150, 30, 0],     # idle 130-150
                     ["decode_block", 260, 40, 0],     # idle 180-260
                     ["decode_block", 320, 20, 0]],    # idle 300-320
        "ops": {},
        "spans": [["bench.window", 100, 260]],         # idle 340-360
        "serve": [["serve.step", 100, 95],
                  ["serve.prefill", 105, 40],
                  ["serve.prefill.wait", 128, 7],
                  ["serve.decode.wait", 150, 33],
                  ["serve.frontend.deliver", 196, 30],
                  ["serve.step", 250, 60],
                  ["serve.decode.unroll", 301, 9],
                  ["serve.step", 365, 10]],
    }


def test_gaps_labelled_by_innermost_serve_span(synthetic):
    spans = synthetic["serve"]
    assert ph.idle_intervals(synthetic) == [(130, 150), (180, 260),
                                            (300, 320), (340, 360)]
    # midpoints 140 (the step and its prefill: the prefill is innermost),
    # 220 (the frontend's delivery), 310 (the unroll), 350 (no span,
    # steps on both sides); equal gaps keep their order in time
    assert ph.host_gaps(synthetic, spans) == [
        ("serve.frontend.deliver", 80), ("serve.prefill", 20),
        ("serve.decode.unroll", 20), (ph.BETWEEN, 20)]
    assert ph.host_gaps(synthetic, spans, n=1) == [
        ("serve.frontend.deliver", 80)]
    # shares split each gap at span edges: 130-135 prefill.wait, 135-145
    # prefill, 145-150 step;
    # 180-183 decode.wait, 183-195 step, 195-196 between, 196-226
    # deliver, 226-250 between, 250-260 step; 300-301 step, 301-310
    # unroll, 310-320 between; 340-360 between (of 140 ns)
    shares = ph.idle_by_label(synthetic, spans)
    want = {ph.BETWEEN: 55, "serve.frontend.deliver": 30, "serve.step": 28,
            "serve.prefill": 10, "serve.decode.unroll": 9,
            "serve.prefill.wait": 5, "serve.decode.wait": 3}
    assert list(shares) == list(want)
    assert shares == {k: pytest.approx(100 * v / 140) for k, v in want.items()}


def test_label_outside_every_step(synthetic):
    spans = synthetic["serve"]
    assert ph.label(90, spans) == "no span"           # before the first step
    assert ph.label(400, spans) == "no span"          # after the last
    assert ph.label(240, spans) == ph.BETWEEN
    assert ph.label(240, spans[:2]) == "no span"      # no step after it


def test_step_idle_counts_steps_starting_in_the_window(synthetic):
    # idle 20 + 80 + 20 + 20 = 140 ns over the steps at 100 and 250 (the
    # one at 365 starts after the window)
    assert ph.step_idle_ms(synthetic, synthetic["serve"]) == \
        pytest.approx(140e-6 / 2)
    assert ph.step_idle_ms(synthetic, []) is None


class _Ev:
    def __init__(self, rid, stage, t):
        self.rid, self.stage, self.t = rid, stage, t


def _events():
    # epoch 100: request 0 runs its whole life inside [101, 110]; request
    # 1 was queued before the window and is admitted in it; request 2
    # only reaches the queue after it
    rows = [(0, "enqueue", 1.0), (0, "submit", 1.5), (0, "admit", 2.0),
            (0, "prefill_done", 4.0), (0, "first_token", 5.0),
            (0, "finish", 6.0),
            (1, "enqueue", -3.0), (1, "submit", -2.9), (1, "admit", 3.0),
            (2, "enqueue", 11.0), (2, "submit", 11.1)]
    return [_Ev(*r) for r in rows] + [object()]       # a device-call event


def test_phase_waits_clip_to_the_window():
    by_rid = ph.stamps(_events(), 100.0)
    assert by_rid[0]["submit"] == 101.5 and set(by_rid) == {0, 1, 2}
    waits = ph.phase_waits(by_rid, 101.0, 110.0)
    assert waits == {"inbox": [pytest.approx(0.5)],
                     "pending": [pytest.approx(0.5), pytest.approx(5.9)],
                     "prefill": [pytest.approx(2.0)],
                     "first_block": [pytest.approx(1.0)]}
    m = ph.phase_metrics(waits)
    assert m["inbox_wait_p90_ms"] == pytest.approx(500.0)
    assert m["pending_wait_p90_ms"] == pytest.approx(500 + 0.9 * 5400)
    assert m["first_block_p90_ms"] == pytest.approx(1000.0)


@pytest.mark.parametrize("lo,hi", [(90.0, 95.0), (120.0, 130.0)])
def test_phase_metrics_of_an_empty_window_are_left_out(lo, hi):
    waits = ph.phase_waits(ph.stamps(_events(), 100.0), lo, hi)
    assert all(v == [] for v in waits.values())
    assert ph.phase_metrics(waits) == {}
    assert ph.phase_metrics(ph.phase_waits({}, 0.0, 1e9)) == {}


def test_ttft_residual_splits_lateness_and_delivery():
    by_rid = ph.stamps(_events(), 100.0)
    done = Record(req=None, due=100.9, times=[105.02, 105.5])
    queued = Record(req=None, due=96.9)               # no token yet
    late = Record(req=None, due=96.95, times=[111.0])  # after the window
    rid_due = {0: 100.9, 1: 96.9, 2: None, 7: 96.95}
    res = ph.ttft_residuals([done, queued, late], rid_due, by_rid,
                            101.0, 110.0)
    # TTFT 4.12 s less phases 4.0 s: 0.1 s late to enqueue, 0.02 s to
    # reach the client
    assert res == [(pytest.approx(0.12), pytest.approx(0.1),
                    pytest.approx(0.02))]


def test_import_loads_no_accelerator_library():
    code = ("import sys; import bench.phases; "
            "assert not [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'libtpu')]")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=120)


@pytest.fixture(scope="module")
def tiny():
    return (json.loads((DATA / "tiny.json").read_text()),
            json.loads((DATA / "tiny_traffic.json").read_text()))


@pytest.mark.parametrize("program_tracer", [True, False])
def test_traced_window_with_the_program_tracer(tiny, tmp_path, monkeypatch,
                                               program_tracer):
    """A traced window of the tiny cell: with the program's tracer on the
    phases, step idle time and residuals are read; off, only what
    bench/run.py's traced path reads is there."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.Peaks(1e12, 1e11))
    cfg, spec = tiny
    save = tmp_path / "excerpt.json"
    res = ph.traced_run("tiny", cfg, spec, seed=2**35 + 11, seconds=2.0,
                        program_tracer=program_tracer,
                        devices=jax.devices()[:1],
                        logdir=str(tmp_path / "trace"), save=str(save))
    json.dumps(res)
    assert not (tmp_path / "trace").exists()
    m = res["metrics"]
    assert {"queue_wait_p90_ms", "device_idle_share"} <= set(m)
    new = set(ph.METRICS) | {"step_idle_ms"}
    if not program_tracer:
        assert res["serve_spans"] == 0 and not new & set(m)
        assert not save.exists()
        return
    assert res["serve_spans"] > 0 and res["dropped_events"] == 0
    assert new <= set(m), m
    assert all(v >= 0 for k, v in m.items() if k in new)
    assert res["host_gaps"] and all(
        lb.startswith("serve.") or lb in (ph.BETWEEN, "no span")
        for lb, _ in res["host_gaps"])
    assert sum(res["idle_by_label"].values()) == pytest.approx(100.0)
    # every request whose first token reached its client in the window
    # is accounted for, lateness and delivery both non-negative
    assert res["ttft_residual_ms"]
    for total, late, deliver in res["ttft_residual_ms"]:
        assert late >= 0 and deliver >= 0
        assert total == pytest.approx(late + deliver)
    ex = json.loads(save.read_text())
    assert ex["serve"] and ex["spans"][0][0] == "bench.window"


def test_recorded_step_to_step_gap():
    # 1.5 s of a traced qwen1.5-0.5b.m4.chat window on one TPU v5e with
    # the program's tracer on, as phases.excerpt gives it: two K=8
    # decode blocks and the device-idle gap between them, with the
    # serve.* spans of the end of one step and the start of the next.
    # bench/probe.py's decode wrapper waits for the block inside
    # serve.decode.dispatch, so the gap opens under that span
    rec = json.loads((DATA / "trace_v5e_serve.json").read_text())
    spans = rec["serve"]
    (block, nxt), = [(a[1] + a[2], b[1]) for a, b in
                     zip(rec["programs"], rec["programs"][1:])]
    assert ph.host_gaps(rec, spans) == [("serve.decode.dispatch",
                                         nxt - block)]
    assert nxt - block == 6_343_800
    # one step starts inside the excerpt
    assert ph.step_idle_ms(rec, spans) == pytest.approx(6.3438)
    shares = ph.idle_by_label(rec, spans)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert set(shares) <= {s[0] for s in spans} | {ph.BETWEEN}
    assert list(shares)[:5] == ["serve.decode.dispatch",
                                "serve.decode.prepare", "serve.decode.unroll",
                                "serve.decode.wait", ph.BETWEEN]
    # the executor hand-off and the event loop's turn: 570 us of 6.34 ms
    assert shares[ph.BETWEEN] == pytest.approx(100 * 569_920 / 6_343_800)
