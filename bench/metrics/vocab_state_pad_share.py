"""The share of the vocabulary state's entries that no column's range
asks for: 100 × (1 − vocab.range_entries / vocab.state_entries), read
from the program's own counters in this process after the traced job.
A fall back to a padded rectangle shows here. In percent."""

import vocab_state


def read(ctx):
    if ctx["kind"] != "offline":
        return None
    from repro import obs

    return vocab_state.pad_share(obs.metrics())
