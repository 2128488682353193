"""TPC-H, as the benchmark holds it: a seeded generator (``datagen``),
the declared column types (``schema.json``) and one module per query
(``q6``, ``q1``, ``q3``) with the columns the query reads, its plain
numpy reference, the float32 control of that reference and the
canonical order of its rows.  Nothing here imports the program.

A later PR adds a query by adding ``<query>.py`` beside these, and
``<query>.plan.json`` (a catalyst ``executedPlan.toJSON`` dump authored
from the query's text) where a configuration's ``entry`` is
``catalyst``; ``q6.plan.json`` is one, driven by the tests and read once
on the chip (``PERF.md`` section 4), in no cell.  A new suite is a
directory beside this one with the same four kinds of file
(``datagen.py``, ``schema.json``, query modules, plan files), a
configuration with ``suite``, ``schema`` and ``entry``, a traffic file
and the manifest's entries: see ``bench/run.py``.
"""
