"""Host seconds per query outside the plan program: the query's wall less
the plan runtime's execute span (padding, plan lookup, governor admission,
the download of the counts).  Mean over the window's queries."""


def read(ctx):
    q = ctx["queries"]
    return sum(r["wall_s"] - (r["after"]["execute_s"] - r["before"]["execute_s"])
               for r in q) / len(q)
