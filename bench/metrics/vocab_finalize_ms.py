"""Device milliseconds per job, per chip, of every program of a traced
offline job that is neither loop 1 nor loop 2: ``vocab.finalize``, on
several chips the ``vocab.merge_tree`` merge and the vocabulary's
replication, and the state's initialization."""

import devtrace


def read(ctx):
    if ctx["kind"] != "offline":
        return None
    other = lambda p: not (devtrace.is_loop1(p) or devtrace.is_loop2(p))
    secs, _ = devtrace.program_seconds(ctx["trace"], ctx["window"], other)
    if secs <= 0:
        return None
    n_dev = max(len(ctx["trace"]["devices"]), 1)
    return 1e3 * secs / n_dev / ctx["jobs"]
