"""Mean milliseconds, over the serving window's requests, from submit to
the moment the service loop took each one out of its ingress or carry
into a batch (the service's ``stream/request`` records)."""

import progspans


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    rec = progspans.window_records(ctx)
    if not rec or not rec["requests"]:
        return None
    return sum(r["taken"] - r["submit"] for r in rec["requests"]) / len(rec["requests"]) / 1e6
