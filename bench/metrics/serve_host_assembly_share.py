"""Share of the service loop's wall time that its ``StallClock`` charged
to ``host_assembly`` (coalescing and packing micro-batches on the host),
over the whole serving window. In percent."""


def read(ctx):
    if ctx["kind"] != "serve" or ctx["stall"]["wall"] <= 0:
        return None
    return 100.0 * ctx["stall"]["host_assembly"] / ctx["stall"]["wall"]
