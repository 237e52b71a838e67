"""Host seconds per query inside the query's ``task`` root span that no
child span names: the root's duration less the union of its children's
intervals.  None where the program records no such spans."""

from benchmark import spans


def read(ctx):
    ts = spans.window_tasks(ctx)
    return None if ts is None else sum(t["self_s"] for t in ts) / len(ts)
