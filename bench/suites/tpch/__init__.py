"""TPC-H, as the benchmark holds it: a seeded generator (``datagen``),
the declared column types (``schema.json``) and one module per query
(``q6``, ``q1``, ``q3``) with the columns the query reads, its plain
numpy reference, the float32 control of that reference and the
canonical order of its rows.  Nothing here imports the program.

A later PR adds a query by adding ``<query>.py`` beside these.
"""
