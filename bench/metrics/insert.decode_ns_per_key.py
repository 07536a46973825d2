"""Device time under the ``qf.decode`` scope (the table's slot-stream
decode before the merge), per key inserted in the window."""

import scopes


def read(record, reduced, peaks):
    return scopes.ns_per_key(record, reduced, "qf.decode")
