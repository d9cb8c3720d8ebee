"""Request bytes over bucket bytes, summed over the window's batches: the
share of the padded utf8 bytes loop 2 decodes that requests filled (the
service's ``stream/batch`` records). In percent."""

import progspans


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    rec = progspans.window_records(ctx)
    if not rec:
        return None
    cap = sum(b["bucket_bytes"] for b in rec["batches"])
    if cap <= 0:
        return None
    return 100.0 * sum(b["bytes"] for b in rec["batches"]) / cap
