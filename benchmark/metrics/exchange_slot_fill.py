"""The exchange's useful share, in %: the real rows the window's queries
sent through the Exchange over the slots of the receive buffers that the
sort after it runs over, summed over every plan the window looked up (each
Exchange's dp x dp x capacity as the plan that ran has it, so a split's
halved capacity and a grow's retries count).  None without an Exchange."""


def read(ctx):
    slots = sum(p["exchange_slots"] for p in ctx["plans"])
    rows = ctx["facts"].get("exchange_rows")
    if not slots or not rows:
        return None
    return 100.0 * rows * len(ctx["queries"]) / slots
