"""Rows scanned per round over corpus rows, total over total
(``ShardedSearchDriver.stats["items"]`` of each round in the window)."""


def read(r):
    rounds = r.counters["rounds"]
    if not rounds:
        return None
    n = r.cfg["num_passages"]
    return 100.0 * sum(x["items"] for x in rounds) / (n * len(rounds))
