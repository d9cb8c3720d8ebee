"""Mean milliseconds, over the window's batches, from outputs ready on
the device to routed: the outputs copied to the host and sliced per
request (the service's ``stream/batch`` records)."""

import progspans


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    rec = progspans.window_records(ctx)
    if not rec or not rec["batches"]:
        return None
    return sum(b["routed"] - b["ready"] for b in rec["batches"]) / len(rec["batches"]) / 1e6
