"""Seconds per query in the memory governor's admission: the ``admit``
children of each window query's ``task`` root span (``budget.acquire``,
any blocked wait included).  None where the program records no such
spans."""

from benchmark import spans


def read(ctx):
    return spans.seconds_per_query(ctx, "admit")
