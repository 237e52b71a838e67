"""Device milliseconds of the sort ops (by HLO opcode) per query, from
the trace, averaged over the chips.  None where the trace shows none."""

from benchmark import trace

PATTERN = r"^sort$"


def read(ctx):
    s = ctx["trace"]
    if s is None:
        return None
    secs = trace.seconds_matching(s.kind_s, PATTERN)
    return 1e3 * secs / len(ctx["queries"]) if secs > 0 else None
