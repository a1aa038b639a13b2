"""One reader per metric: ``bench/metrics/<name>.py`` defines ``read(ctx)``.

``ctx`` is ``bench.run.Ctx``. A reader returns the metric's value, or None
where the run holds nothing for it to read; the metric is then left out of
the result line. Per-layer readers take their numbers from the trace
(``ctx.trace``, see ``bench/trace.py``) or from the program's counters
(``ctx.counters``); end-to-end readers from the host clock.
"""
