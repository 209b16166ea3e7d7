"""Share of the window's driver rounds that scanned the corpus in place
(``ShardedSearchDriver.stats["executor"] == "resident"``): the device
corpus read inside the jitted scan, with no per-chunk load, pad or
stack on the host."""


def read(r):
    rounds = r.counters["rounds"]
    if not rounds:
        return None
    resident = sum(x.get("executor") == "resident" for x in rounds)
    return 100.0 * resident / len(rounds)
