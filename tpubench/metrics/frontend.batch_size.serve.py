"""Mean queries per micro-batch the frontend coalesced in the window
(``ServeFrontend.stats``: queries over batches)."""


def read(r):
    before, after = r.counters["frontend"]
    batches = after["batches"] - before["batches"]
    if batches <= 0:
        return None
    return (after["queries"] - before["queries"]) / batches
