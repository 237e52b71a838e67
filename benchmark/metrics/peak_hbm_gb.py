"""Device memory the window's largest plan takes, in GB (1e9 B), summed
over its chips: the compiled plan's ``memory_analysis()`` (arguments,
outputs and temporaries, less what outputs alias), per chip times its
chips.  ``memory_stats()["peak_bytes_in_use"]`` is not read: on the TPU it
leaves out the program's temporaries (PERF.md).  None when no plan that
ran has an analysis."""


def read(ctx):
    sizes = [p["bytes_per_chip"] * p["chips"] for p in ctx["plans"]
             if p["bytes_per_chip"] is not None]
    return max(sizes) / 1e9 if sizes else None
