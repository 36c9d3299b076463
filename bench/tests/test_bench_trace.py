"""The reduction from a profiler trace to device times, on a synthetic trace
and on a trimmed trace recorded on a TPU v5e."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).resolve().parent / "data" / "trace_v5e.json"


@pytest.fixture
def synthetic():
    return {
        "devices": 1,
        "programs": [["before", 10, 20, 0],            # ends before the window
                     ["decode_block", 100, 50, 0],
                     ["prefill_chunk", 120, 40, 0],    # overlaps the decode
                     ["decode_block", 200, 30, 0],
                     ["decode_block", 290, 40, 0]],    # runs past the window
        "ops": {},
        "spans": [["bench.window", 50, 250],
                  ["bench.step", 90, 100],
                  ["bench.prefill", 170, 20],
                  ["bench.step", 240, 50]],
    }


def test_union_and_idle_share(synthetic):
    assert tr.window(synthetic) == (50, 300)
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 20)]) == \
        [(0, 4), (5, 12)]
    # busy: [100, 160] + [200, 230] + [290, 300] inside [50, 300]
    assert tr.busy_ns(synthetic) == 60 + 30 + 10


def test_program_time_by_name(synthetic):
    assert tr.program_ns(synthetic) == {"decode_block": 50 + 30 + 10,
                                        "prefill_chunk": 40}
    table = {"jit__block_impl": "decode_block"}
    assert tr.program_name("jit__block_impl(7)", table) == "decode_block"
    assert tr.program_name("jit_other(12)", table) == "jit_other"


def test_two_devices_average(synthetic):
    two = dict(synthetic, devices=2,
               programs=synthetic["programs"] + [["decode_block", 50, 250, 1]])
    assert tr.busy_ns(two) == (100 + 250) / 2


def test_gaps_labelled_by_innermost_host_span(synthetic):
    # gaps [50,100] (mid 75: no span), [160,200] (mid 180: step and its
    # prefill; the prefill is innermost), [230,290] (mid 260: step)
    assert tr.idle_gaps(synthetic) == [("bench.step", 60), ("no span", 50),
                                       ("bench.prefill", 40)]
    assert tr.idle_gaps(synthetic, n=1) == [("bench.step", 60)]


def test_window_span_is_required(synthetic):
    synthetic["spans"] = synthetic["spans"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        tr.window(synthetic)


def test_import_loads_no_accelerator_library():
    code = ("import sys; import bench.trace_reduce; "
            "assert not [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'libtpu')]")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=120)


def test_table_names_the_serving_programs():
    table = tr.load_table()
    assert set(table.values()) == {"decode_block", "prefill_chunk",
                                   "slot_scatter"}


@pytest.fixture
def recorded():
    # 0.76 s of a traced qwen1.5-0.5b.m4.chat window on one TPU v5e, as
    # trace_reduce.extract gives it, cut to the programs and host spans that
    # overlap it (with the 40 longest operations of the whole window):
    # chunked prefill calls, slot scatters and decode blocks
    return json.loads(RECORDED.read_text())


def test_recorded_busy_matches_a_timeline(recorded):
    lo, hi = tr.window(recorded)
    res = 10_000                                  # 10 us cells
    busy = np.zeros((hi - lo) // res, bool)
    for _, s, d, _ in recorded["programs"]:
        busy[max(0, (s - lo) // res): max(0, (s + d - lo) // res)] = True
    assert tr.busy_ns(recorded) == pytest.approx(busy.sum() * res, rel=1e-3)
    gaps = tr.idle_gaps(recorded, n=10_000)
    assert sum(ns for _, ns in gaps) == pytest.approx(
        (hi - lo) - tr.busy_ns(recorded))


def test_recorded_programs_and_labels(recorded):
    by_name = tr.program_ns(recorded)
    assert set(by_name) == {"prefill_chunk", "decode_block", "slot_scatter"}
    # eleven whole chunk calls of 46 ms each fall in this window
    n_chunks = sum(p[0] == "prefill_chunk" for p in recorded["programs"])
    assert n_chunks == 11
    assert by_name["prefill_chunk"] == pytest.approx(11 * 46.05e6, rel=2e-3)
    gaps = tr.idle_gaps(recorded)
    assert [ns for _, ns in gaps] == sorted((ns for _, ns in gaps),
                                            reverse=True)
    assert {label for label, _ in gaps} <= {"bench.step", "bench.prefill",
                                            "bench.decode", "bench.submit",
                                            "no span"}


def test_leaf_ops_leave_out_loops(recorded):
    assert any(" while(" in text for text in recorded["ops"])
    ops = tr.leaf_ops(recorded)
    assert ops and not any(k.startswith(tr.CONTAINERS) for k in ops)
    assert tr.op_parts("%convert.9 = f32[2,3]{1,0} convert(bf16[2,3]{1,0} "
                       "%p)") == ("%convert.9", "f32[2,3]{1,0}", "convert")
