"""Real input fact rows of all queries completed in the window, over the
time from the window's start to the last completion (host clock).  Padding
rows do not count.  For queries of fixed size, query_s = rows / this."""


def read(ctx):
    return ctx["rows_per_query"] * len(ctx["queries"]) / ctx["window_s"]
