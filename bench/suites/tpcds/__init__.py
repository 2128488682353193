"""TPC-DS, as the benchmark holds it: a seeded generator at the
specification's SF1 cardinalities (``datagen``), the declared column
types (``schema.json``), one module per query (``q7``: the columns it
reads, its plain numpy reference, the float32 control of that reference,
the canonical order of its rows, a float column's ``TOLERANCE``) and,
beside each, the plan as Spark 3.5.1 hands it over (``q7.plan.json``, a
catalyst ``executedPlan.toJSON`` dump written by ``gen_q7_plan.py`` from
the query's text).  Nothing here imports the program.
"""
