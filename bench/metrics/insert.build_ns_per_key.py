"""Device time under the ``qf.build`` scope (position scan, the
``qf_build`` kernel and the ``occ`` scatter), per key inserted in the
window."""

import scopes


def read(record, reduced, peaks):
    return scopes.ns_per_key(record, reduced, "qf.build")
