"""The plan program's share of its roofline, in %.

The least time a query could take is the bytes it must move (each scanned
column read once, the counts written: the query driver's ``min_bytes``)
over the chips' published HBM bandwidth; q97 does no arithmetic worth a
bound of its own.  That over the device time of the programs the window ran
per query (the trace's module events, averaged over the chips)."""


def read(ctx):
    s = ctx["trace"]
    if s is None or not ctx["facts"].get("min_bytes"):
        return None
    device_s = sum(s.module_s.values()) / len(ctx["queries"])
    if device_s <= 0:
        return None
    least_s = ctx["facts"]["min_bytes"] / (
        ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / device_s
