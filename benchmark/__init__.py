"""The on-chip benchmark of apex_tpu: the yardstick later PRs are held to.

Everything that measures lives here, so that a PR which changes the program
cannot change how it is measured: traffic generation (``traffic_gen.py``), the
timed loop and the result line (``run.py``), the reduction from a profiler
trace to metrics (``trace_reduce.py``), the table of published peaks
(``peaks.json``), the FLOP formulas (``flops.py``), a plain float32
reference of every configuration (``reference/``) and the comparison that
decides ``correct`` (``compare.py``).

It is driven by data.  A configuration is ``configs/<name>.json`` plus the
family adapter it names (``families/<family>.py``); a traffic mix is
``traffic/<name>.json``; a metric is ``end_to_end/<name>.py`` or
``layer_metrics/<name>.py``.  ``run.py`` holds none of those names: it finds
each through ``BENCHMARK.json``.  Adding a cell, a configuration or a metric
is adding files and entries, never editing one.
"""
