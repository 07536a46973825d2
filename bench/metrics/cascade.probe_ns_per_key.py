"""Device time under the ``cascade.probe`` scope less the
``cascade.exact`` fallback nested in it (hash, query sort, plane casts
and blocking, the ``cascade_probe`` kernel, unpermute), per query
answered in the window."""

import cascade_scopes


def read(record, reduced, peaks):
    return cascade_scopes.ns_per_key(
        record, reduced, "cascade.probe", less="cascade.exact"
    )
