"""Per-layer metric readers, one module per metric of BENCHMARK.json.

Each module defines ``read(ctx) -> float | None``; ``ctx`` is a
:class:`bench.layers.LayerContext`.  A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""
