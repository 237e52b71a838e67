"""Seconds per query padding the scan tables onto the plan's pow2 lattice:
the ``plan_pad`` children of each window query's ``task`` root span.
None where the program records no such spans."""

from benchmark import spans


def read(ctx):
    return spans.seconds_per_query(ctx, "plan_pad")
