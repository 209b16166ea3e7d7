"""Share of the encoder's token slots that were padding in the window
(``EncodePipeline.stats``: 1 - tokens_real / tokens_padded)."""


def read(r):
    before, after = r.counters["pipeline"]
    padded = after["tokens_padded"] - before["tokens_padded"]
    if padded <= 0:
        return None
    return 100.0 * (1.0 - (after["tokens_real"] - before["tokens_real"])
                    / padded)
