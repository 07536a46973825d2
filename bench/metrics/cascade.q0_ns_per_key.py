"""Device time under the ``cascade.q0`` scope (Q0 absorbs the batch:
its decode, the sort with the batch and its rebuild), per key inserted
in the window."""

import cascade_scopes


def read(record, reduced, peaks):
    return cascade_scopes.ns_per_key(record, reduced, "cascade.q0")
