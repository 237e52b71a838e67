"""Peak bytes the memory governor's default device budget held reserved
over the window (``BudgetedResource.reset_peak``), in GB (1e9 B)."""


def read(ctx):
    peak = ctx["governor_peak_bytes"]
    return peak / 1e9 if peak else None
