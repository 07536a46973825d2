"""Queries answered per second: all keys of the window's completed
batches over the host-clock seconds those batches took."""


def read(record, reduced, peaks):
    b = record["batches"]
    return sum(x["keys"] for x in b) / sum(x["dt"] for x in b) if b else None
