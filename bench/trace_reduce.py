"""From the profiler's trace to times: the reduction every run uses.

``extract`` reads an ``.xplane.pb`` into a small, plain trace:

* ``programs``: ``[name, start_ns, duration_ns, device]`` for each execution
  of a device program (the device planes' "XLA Modules" line), named by the
  table in ``programs.json``; a program not in the table keeps its own name;
* ``ops``: device time summed by operation name (the "XLA Ops" line);
* ``spans``: ``[name, start_ns, duration_ns]`` for the benchmark's host spans
  (``bench.*``, see ``probe.py``).  ``bench.window`` bounds the traced window.

All times are on the profiler's one clock.  The functions below reduce such
a trace; they load no TPU library and are tested on a recorded one.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "programs.json"


def load_table(path: Path = TABLE) -> dict[str, str]:
    """jit name -> benchmark name."""
    raw = json.loads(Path(path).read_text())
    return {jit: name for name, jits in raw.items() if not name.startswith("_")
            for jit in jits}


def program_name(module: str, table: dict[str, str]) -> str:
    base = re.sub(r"\(\d+\)$", "", module)
    return table.get(base, base)


def find_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {logdir}")
    return found[0]


def extract(path: str, table: dict[str, str]) -> dict:
    from jax.profiler import ProfileData

    programs, spans, ops = [], [], collections.Counter()
    devices = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" not in lines:
                continue
            for ev in lines["XLA Modules"].events:
                programs.append([program_name(ev.name, table),
                                 int(ev.start_ns), int(ev.duration_ns), devices])
            devices += 1
            if "XLA Ops" in lines:
                for ev in lines["XLA Ops"].events:
                    ops[ev.name] += int(ev.duration_ns)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {"devices": devices, "programs": sorted(programs, key=lambda p: p[1]),
            "ops": dict(ops.most_common()), "spans": spans}


def window(trace: dict) -> tuple[int, int]:
    win = [s for s in trace["spans"] if s[0] == "bench.window"]
    if len(win) != 1:
        raise ValueError(f"{len(win)} bench.window spans in the trace")
    _, start, dur = win[0]
    return start, start + dur


def _clip(start, dur, lo, hi):
    return max(start, lo), min(start + dur, hi)


def union(intervals) -> list[tuple[int, int]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: dict) -> float:
    """Device busy time within the window: on each device the union of its
    program intervals, averaged over the devices."""
    lo, hi = window(trace)
    total = 0
    for dev in range(trace["devices"]):
        total += sum(b - a for a, b in union(
            _clip(s, d, lo, hi) for _, s, d, k in trace["programs"] if k == dev))
    return total / max(1, trace["devices"])


def program_ns(trace: dict) -> dict[str, int]:
    """Device time by program name within the window."""
    lo, hi = window(trace)
    out: collections.Counter = collections.Counter()
    for name, s, d, _ in trace["programs"]:
        a, b = _clip(s, d, lo, hi)
        if b > a:
            out[name] += b - a
    return dict(out.most_common())


def idle_gaps(trace: dict, n: int = 10) -> list[tuple[str, int]]:
    """The ``n`` longest gaps in the window in which no device ran a
    program, each labelled with the innermost benchmark host span (the
    shortest one) that covers the gap's midpoint, or "no span" where the
    host was in none."""
    lo, hi = window(trace)
    busy = union(_clip(s, d, lo, hi) for _, s, d, _ in trace["programs"])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [s for s in trace["spans"] if s[0] != "bench.window"]
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        inside = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        label = min(inside, key=lambda s: s[2])[0] if inside else "no span"
        out.append((label, b - a))
    return out


_HLO = re.compile(r"(%\S+) = (.*?[)}\]]) ([a-z][\w-]*)\(")
CONTAINERS = ("while", "call", "conditional")


def op_parts(text: str) -> tuple[str, str, str]:
    """(name, result shape, kind) of an HLO instruction's text."""
    m = _HLO.match(text)
    if m is None:
        return text.split(" ", 1)[0], "", ""
    return m.group(1), m.group(2), m.group(3)


def leaf_ops(trace: dict) -> dict[str, int]:
    """Device time by operation, leaving out the loops and calls that hold
    other operations (the operations inside count that time already); each
    is named ``<kind> <name> <result shape>``."""
    out: collections.Counter = collections.Counter()
    for text, ns in trace["ops"].items():
        name, shape, kind = op_parts(text)
        if kind in CONTAINERS:
            continue
        shape = "(tuple)" if shape.startswith("(") else shape
        out[f"{kind} {name} {shape}"[:160]] += ns
    return dict(out.most_common())
