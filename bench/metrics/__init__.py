"""One reader per per-layer metric, found by the metric's name.

Each module defines ``read(ctx) -> float | None``. ``ctx`` holds the traced
window of a ``--trace 1`` run (see ``bench/harness.py``): ``trace``,
``lo``/``hi`` (the window on the trace's clock, ns), ``steps``,
``window_s``, ``times`` (``bench.trace.ChipTimes`` in ns, averaged over the
chips), ``input_s``, ``compile_s``, ``chips``, ``model_flops`` (per step)
and ``peak_flops`` (per chip). A reader that finds nothing to read returns
``None``, and the metric is left out of the result.
"""
