"""Seconds per query in the plan runtime's execute span (upload through
block_until_ready of the compiled plan): the delta of the plan cache's
``execute_s``.  Mean over the window's queries; none when no plan ran."""


def read(ctx):
    q = ctx["queries"]
    total = sum(r["after"]["execute_s"] - r["before"]["execute_s"] for r in q)
    return total / len(q) if total > 0 else None
