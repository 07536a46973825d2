"""Device time under the ``cascade.exact`` scope (each structure's
exact fallback: its whole-table decode and binary search), per query
answered in the window."""

import cascade_scopes


def read(record, reduced, peaks):
    return cascade_scopes.ns_per_key(record, reduced, "cascade.exact")
