"""Share of one traced offline job's wall time in which no operation ran
on the device (averaged over the chips): 1 - busy / window, in percent."""


def read(ctx):
    if ctx["kind"] != "offline" or ctx["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
