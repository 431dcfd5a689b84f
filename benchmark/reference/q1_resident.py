"""The reference of ``queries/q1_resident.py``: Q1's, as it is. The text is
Q1's and the rows it is held to are the ones ``reference/q1.py`` works out
from the Parquet files through pyarrow, never from the cache: a cached row
that differs from its file fails ``correct``. Nothing of the program."""

from benchmark.reference.q1 import COLUMNS, reference

__all__ = ["COLUMNS", "reference"]
