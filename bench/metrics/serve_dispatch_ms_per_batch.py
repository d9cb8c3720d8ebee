"""Mean milliseconds, over the window's batches, from assembled to the
return of the dispatch call: the row count, the upload and the launch
of the bucket's loop-2 program (the service's ``stream/batch`` records)."""

import progspans


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    rec = progspans.window_records(ctx)
    if not rec or not rec["batches"]:
        return None
    return sum(b["dispatched"] - b["assembled"] for b in rec["batches"]) / len(rec["batches"]) / 1e6
