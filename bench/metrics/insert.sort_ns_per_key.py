"""Device time under the ``qf.sort`` scope (the sort of the table's
fingerprints with the batch's), per key inserted in the window."""

import scopes


def read(record, reduced, peaks):
    return scopes.ns_per_key(record, reduced, "qf.sort")
