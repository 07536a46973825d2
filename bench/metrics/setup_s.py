"""Seconds from the start of the process to the start of the window:
imports, device start, compiles (or compile-cache loads), key
generation, the set-up load and its checks."""


def read(record, reduced, peaks):
    return record["setup_s"]
