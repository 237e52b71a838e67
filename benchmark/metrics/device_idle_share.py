"""1 - (union of the intervals in which an op ran on the device) / (the
traced window), in %, averaged over the chips."""


def read(ctx):
    s = ctx["trace"]
    if s is None:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
