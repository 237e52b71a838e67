"""Plan-cache misses plus XLA backend compiles inside the window.  Every
shape is warmed up in set-up, so this should be 0."""


def read(ctx):
    c = ctx["compiles"]
    return c["plan_misses"] + c["backend_compiles"]
