"""Share of a traced stretch of the serving window in which no operation
ran on the device: 1 - busy / window, in percent."""


def read(ctx):
    if ctx["kind"] != "serve" or ctx["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
