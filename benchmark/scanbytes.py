"""Bytes a query has to read, from its text and the schema alone.

rows(table) x stored width of every column the query text names: the same
work whatever plan or kernel does it. Widths are those of the columns as
they are held for scanning: 8 bytes for bigint and double (the spec's
decimal(15,2)), 4 for int and date, 4 for a string (its dictionary code).
"""
import re

WIDTH = {"int64": 8, "double": 8, "int32": 4, "date32[day]": 4, "string": 4}


def width_of(arrow_type) -> int:
    name = str(arrow_type)
    if name.startswith("dictionary"):
        name = "string"
    return WIDTH[name]


def columns_named(sql: str, schemas: dict) -> dict:
    """{table: [columns of it that `sql` names]}, in schema order."""
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", sql.lower()))
    named = {t: [f.name for f in schema if f.name.lower() in words]
             for t, schema in schemas.items()}
    return {t: cols for t, cols in named.items() if cols}


def query_bytes(sql: str, schemas: dict, rows: dict) -> int:
    return sum(rows[t] * sum(width_of(schemas[t].field(c).type) for c in cols)
               for t, cols in columns_named(sql, schemas).items())


def query_rows(sql: str, schemas: dict, rows: dict) -> int:
    """Rows of the tables the query reads."""
    return sum(rows[t] for t in columns_named(sql, schemas))
