"""``"entry": "catalyst"``: the plan arrives as Spark hands it over, a
catalyst ``executedPlan.toJSON`` dump, and goes through the program's
front door on every query: ``BlazeSparkSession.plan`` = parse
(``spark/plan_json.py``) -> strategy (``spark/strategy.py``) ->
conversion (``spark/converters.py``).

The dump is the benchmark's: ``bench/suites/<suite>/<query>.plan.json``,
authored from the query's text in Spark's own encoding, not emitted
from the program's IR.  Every ``FileSourceScanExec`` in it names a table
of the query module's ``COLUMNS`` and outputs columns of that list: the
converter looks each up in the pruned scan registered here.
"""

import os

SUITES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "suites")


def source(suite, query, scans, n_parts):
    from blaze_tpu.spark.session import BlazeSparkSession

    session = BlazeSparkSession(default_parallelism=n_parts)
    for table, scan in scans.items():
        session.register_table(table, scan)
    with open(os.path.join(SUITES, suite, query + ".plan.json")) as f:
        text = f.read()
    # the text, not a parsed tree: Spark sends a plan per query, so the
    # parse is inside the timed plan span every time
    return lambda: session.plan(text)
