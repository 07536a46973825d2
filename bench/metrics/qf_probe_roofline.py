"""Least time of the window's probes over the measured time of the
``qf_probe`` kernel, in %.  The least time counts the bytes a probe
needs (``roofline.probe_bytes``) at the chip's HBM bandwidth."""

import roofline


def read(record, reduced, peaks):
    kernel_s = (reduced or {}).get("kernel_s", {}).get("qf_probe", 0.0)
    if not kernel_s:
        return None
    t = record["table"]
    need = sum(roofline.probe_bytes(x["keys"], t["slots"], t["r"]) for x in record["batches"])
    return 100.0 * roofline.least_seconds(need, peaks) / kernel_s
