"""One reader per per-layer metric, found by the metric's name.

A reader is a module with ``LAYER``, ``MOVES`` and ``read(run)``.
``run`` is what a ``--trace 1`` run gathered (see ``bench/run.py``:
``queries``, ``counters``, ``plan_s``, ``trace``, ``memory_peak_bytes``,
``least_bytes``, ``peak``).  A reader that finds nothing to read returns
None, and the metric is left out of the result line.
"""
