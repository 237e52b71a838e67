"""Seconds per query in the plan's uploads: the ``plan_upload`` children
of each window query's ``task`` root span.  ``device_put`` is
asynchronous, so this is the enqueue and the host staging copy; a
transfer still in flight shows as device idle inside ``plan_run``.  None
where the program records no such spans."""

from benchmark import spans


def read(ctx):
    return spans.seconds_per_query(ctx, "plan_upload")
