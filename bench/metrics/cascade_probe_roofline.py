"""Least time of the window's fused cascade probes over the device time
of the ``cascade.kernel`` scope (the ``cascade_probe`` launches), in %.
The least time counts, as ``roofline.probe_bytes`` does for one table,
each query's key and answer (5 B) and, for every structure of the
stack, one packed 128-slot row per query, at most that whole structure
(``roofline.packed_bytes``), at the chip's HBM bandwidth."""

import cascade_scopes
import roofline


def read(record, reduced, peaks):
    kernel_s = cascade_scopes.scope_seconds(record, reduced, "cascade.kernel")
    if not kernel_s:
        return None
    need = 0.0
    for x in record["batches"]:
        n = x["keys"]
        need += n * 5 + sum(
            min(n * roofline.packed_bytes(128, r), roofline.packed_bytes(slots, r))
            for slots, r in record["table"]
        )
    return 100.0 * roofline.least_seconds(need, peaks) / kernel_s
