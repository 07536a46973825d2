"""Device time under the ``cascade.collapse`` scope (a full Q0 merged
with levels into the first that fits: decode, sort and build of every
structure it reads, nested ``qf.*`` scopes included), per key inserted
in the window."""

import cascade_scopes


def read(record, reduced, peaks):
    return cascade_scopes.ns_per_key(record, reduced, "cascade.collapse")
