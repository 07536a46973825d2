"""Device time of the ``qf.probe`` scope's own ops (query sort, plane
casts and blocking, the ``qf_probe`` kernel, unpermute; not the exact
fallback nested in it), per query answered in the window."""

import scopes


def read(record, reduced, peaks):
    return scopes.ns_per_key(record, reduced, "qf.probe", own=True)
