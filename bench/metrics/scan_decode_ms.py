"""Host milliseconds a query spends fetching, decompressing and decoding
its files' column chunks: the program's ``scan_decode`` span, one per
row group read (``ParquetScanExec``: the ``read_column_chunk`` calls and
the padding that follows them; staging is ``scan_stage_ms``).  Nothing
where the program has no such span (the parent) or scans no file."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_decode_ns", 1e-6)
