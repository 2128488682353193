"""The least bytes a query has to read from HBM: rows in, times the
declared widths of the columns it references.  Whatever implements the
query reads at least this once, so the figure does not move when the
implementation does."""

import re


def type_width(declared: str) -> int:
    """Bytes a row of one column takes at its declared type.  A string
    takes its declared width plus its 4-byte length."""
    fixed = {"int32": 4, "date32": 4, "float32": 4, "int64": 8, "float64": 8, "timestamp": 8}
    if declared in fixed:
        return fixed[declared]
    m = re.fullmatch(r"decimal\((\d+),\d+\)", declared)
    if m:
        return 8 if int(m.group(1)) <= 18 else 16
    m = re.fullmatch(r"string\((\d+)\)", declared)
    if m:
        return int(m.group(1)) + 4
    raise ValueError(f"no width known for type {declared!r}")


def row_bytes(schema_tables: dict, table: str, columns) -> int:
    return sum(type_width(schema_tables[table][c]) for c in columns)


def least_bytes(schema_tables: dict, columns: dict, rows: dict) -> int:
    """``columns``: table -> names the query references; ``rows``: table
    -> rows scanned."""
    return sum(rows[t] * row_bytes(schema_tables, t, cols) for t, cols in columns.items())
