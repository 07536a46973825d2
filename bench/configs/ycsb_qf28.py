"""Reference counts and table geometry of the flat quotient filter."""

COUNTERS = ("n", "overflow")
SLACK = 1024  # slots past 2^q that absorb the last cluster (qf default)


def fingerprint_bits(make: dict) -> int:
    return make["q"] + make["r"]


def table(make: dict) -> dict:
    return {"slots": (1 << make["q"]) + SLACK, "r": make["r"]}


def expected_counts(make, preload, batch, n_batches) -> dict:
    return {"n": preload + batch * n_batches}


def batch_events(make, before: dict, after: dict) -> dict:
    """Every insert rebuilds the whole table from all stored keys."""
    t = table(make)
    n = int(after["n"])
    return {"builds": [[t["slots"], t["r"], n]]}
