"""Per-layer metric readers, one module per metric named as in
BENCHMARK.json. Each ``read(rec)`` takes the traced run's records
(``rec.calls``: the step calls in the window with their host times and
live positions; ``rec.trace``: the reduced profiler trace; ``rec.model``,
``rec.path`` (precision path), ``rec.groups``, ``rec.kv_bits``,
``rec.peaks``, ``rec.window``, ``rec.tokens``, ``rec.ttft``,
``rec.compile_setup_s``) and returns a number, or None when the run holds
nothing to read."""
