"""Device time under the ``qf.exact`` scope (the probe's exact fallback,
its whole-table decode included), per query answered in the window."""

import scopes


def read(record, reduced, peaks):
    return scopes.ns_per_key(record, reduced, "qf.exact")
