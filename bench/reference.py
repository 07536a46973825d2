"""Plain reference for the filters the benchmark drives (numpy only).

It imports nothing of the program.  A quotient filter of ``p``-bit
fingerprints answers "present" for a query exactly when some stored
key has the query's fingerprint: the top ``p`` bits of a 64-bit hash
whose top 32 bits are a bijection of the 32-bit key.  For ``p >= 32``
two keys share a fingerprint only when they are equal, so the filter's
answer is exact membership, which the benchmark knows by construction
(``keys``): ``in_ranges``.
"""

from __future__ import annotations

import numpy as np

# The control's fingerprint width: the nearest below the 32-bit key
# width, where a filter stops being exact (see ``bench/tests``).
CONTROL_P = 31


def in_ranges(idx: np.ndarray, ranges) -> np.ndarray:
    """Membership by construction: is each record index in one of the
    stored ``[lo, hi)`` ranges?"""
    idx = np.asarray(idx, np.int64)
    out = np.zeros(idx.shape, bool)
    for lo, hi in ranges:
        out |= (idx >= lo) & (idx < hi)
    return out
