"""Device bytes in use (``memory_stats()["bytes_in_use"]``) with only
the filter state live, over the keys it stores; read outside the timed
intervals."""


def read(record, reduced, peaks):
    n = record.get("resident_keys")
    return record["resident_bytes"] / n if n else None
