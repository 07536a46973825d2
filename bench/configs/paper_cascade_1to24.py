"""Reference counts and geometry of the cascade filter (paper section 4).

A plain replay of the collapse rule on counts alone: Q0 absorbs each
batch; once it reaches ``max_load`` of its buckets it merges with
levels 0..i into the first level i whose capacity holds them all, and
levels 0..i-1 and Q0 empty.  With no level that fits, Q0 keeps
absorbing.  Level i has q = ram_q + (i + 1) * log2(fanout) and every
structure keeps ``p`` fingerprint bits, so r = p - q.
"""

import numpy as np

COUNTERS = ("n", "overflow", "level_counts")
MAX_LOAD = 0.75  # the cascade's default operating point


def fingerprint_bits(make: dict) -> int:
    return make["p"]


def _qs(make: dict) -> list:
    """Quotient bits of Q0 and of each level, top down."""
    lb = int(make["fanout"]).bit_length() - 1
    return [make["ram_q"] + i * lb for i in range(make["levels"] + 1)]


def _slots(q: int) -> int:
    return (1 << q) + max(1024, (1 << q) // 64)


def table(make: dict) -> list:
    """``[slots, r]`` of Q0 and of each level, top down."""
    return [[_slots(q), make["p"] - q] for q in _qs(make)]


def preload_batch(make: dict) -> int:
    """The set-up load's batch: 2^(ram_q - 2), a third of Q0's capacity
    (the configuration's ``preload.batch``)."""
    return 1 << (make["ram_q"] - 2)


def _replay(make: dict, batches) -> tuple:
    """``(q0, [level counts])`` after inserting ``batches`` key counts."""
    qs = _qs(make)
    m0 = np.float32(1 << qs[0])
    caps = [int((1 << q) * MAX_LOAD) for q in qs[1:]]
    q0, levels = 0, [0] * make["levels"]
    for k in batches:
        q0 += k
        if np.float32(q0) / m0 < np.float32(MAX_LOAD):
            continue
        cum = q0
        for i, cap in enumerate(caps):
            cum += levels[i]
            if cum <= cap:
                levels[:i] = [0] * i
                levels[i] = cum
                q0 = 0
                break
    return q0, levels


def _schedule(make, preload, batch, n_batches) -> list:
    pb = preload_batch(make)
    sizes = [min(pb, preload - s) for s in range(0, preload, pb)]
    return sizes + [batch] * n_batches


def expected_counts(make, preload, batch, n_batches) -> dict:
    q0, levels = _replay(make, _schedule(make, preload, batch, n_batches))
    return {"n": q0 + sum(levels), "level_counts": levels}


def batch_events(make, before: dict, after: dict) -> dict:
    """Q0 rebuilds on every insert; a collapse into level i (the deepest
    level whose count grew) builds level i from all it then holds."""
    t = table(make)
    lv_before = [int(x) for x in before["level_counts"]]
    lv_after = [int(x) for x in after["level_counts"]]
    q0_after = int(after["n"]) - sum(lv_after)
    grew = [i for i in range(len(lv_after)) if lv_after[i] > lv_before[i]]
    if not grew:
        return {"builds": [[t[0][0], t[0][1], q0_after]]}
    # Q0 was rebuilt with the batch, then emptied by the collapse
    q0_built = int(after["n"]) - sum(lv_before)
    i = grew[-1]
    return {
        "builds": [
            [t[0][0], t[0][1], q0_built],
            [t[i + 1][0], t[i + 1][1], lv_after[i]],
        ]
    }
