"""Bytes a filter kernel needs, from the operation's logical shapes.

They count the work, not what today's kernel happens to move: a table
slot is packed at ``r + 3`` bits (remainder plus the three metadata
bits), a key is 4 bytes and an answer 1.  So a later change of layout
cannot make these stale.
"""


def packed_bytes(slots: int, r: int) -> float:
    return slots * (r + 3) / 8


def probe_bytes(queries: int, slots: int, r: int) -> float:
    """A batched probe reads each key and writes its answer (5 B), and
    reads one 128-slot row of the packed table per query, at most the
    whole table."""
    return queries * 5 + min(queries * packed_bytes(128, r), packed_bytes(slots, r))


def build_bytes(slots: int, r: int, n: int, p: int) -> float:
    """A build writes the whole packed table and reads the ``n`` sorted
    ``p``-bit fingerprints it holds."""
    return packed_bytes(slots, r) + n * p / 8


def least_seconds(nbytes: float, peaks: dict) -> float:
    return nbytes / peaks["hbm_bytes_per_s"]
