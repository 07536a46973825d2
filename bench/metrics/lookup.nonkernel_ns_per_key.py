"""Device time of the lookup program outside Pallas kernels (busy time
that no kernel event covers), per query answered in the window."""


def read(record, reduced, peaks):
    keys = sum(x["keys"] for x in record["batches"])
    if not reduced or not keys:
        return None
    return reduced["nonkernel_s"] * 1e9 / keys
