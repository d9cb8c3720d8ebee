"""Mean milliseconds, over the window's batches, from the end of a
batch's loop-2 program on the device to the service's *ready* stamp
(its ``block_until_ready`` returned). Batches and loop-2 executions are
matched in order: the k-th batch of the window with the k-th execution
that ends after the window's first batch was taken (the service runs one
program per batch, in order). From the service's ``stream/batch``
records and the device trace; none without a device plane."""

import devtrace
import progspans


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["trace"]["devices"]:
        return None
    rec = progspans.window_records(ctx)
    if not rec or not rec["batches"]:
        return None
    device = ctx["trace"]["devices"][min(ctx["trace"]["devices"])]
    first = rec["batches"][0]["taken"]
    ends = sorted(
        float(s + d) for n, s, d in device["modules"] if devtrace.is_loop2(devtrace.program_name(n)) and s + d > first
    )
    lags = [b["ready"] - end for b, end in zip(rec["batches"], ends)]
    if not lags:
        return None
    return sum(lags) / len(lags) / 1e6
