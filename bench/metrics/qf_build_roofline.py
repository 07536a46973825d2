"""Least time of the window's table builds over the measured time of
the ``qf_build`` kernel, in %.  The least time counts the packed table
written and the fingerprints read (``roofline.build_bytes``) at the
chip's HBM bandwidth."""

import roofline


def read(record, reduced, peaks):
    kernel_s = (reduced or {}).get("kernel_s", {}).get("qf_build", 0.0)
    if not kernel_s:
        return None
    p = record["fingerprint_bits"]
    need = sum(
        roofline.build_bytes(slots, r, n, p)
        for x in record["batches"]
        for slots, r, n in x["events"]["builds"]
    )
    return 100.0 * roofline.least_seconds(need, peaks) / kernel_s
