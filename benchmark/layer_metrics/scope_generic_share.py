"""Per-layer metric ``scope_generic_share``: the share of the first
device's busy time in operations whose scope is a GENERIC one: the
executor's ``<op type>.<node>`` around a node that no builder and no op
named (``mxnet_tpu/trace/scopes.py``; the program says which scope is of
which sort, ``scopes.sort_of``, so no list of kinds here goes stale with
the next builder).  It is how much of a step nobody has named: under a
tenth, a ``perf_opt`` issue can be planned from the ledger's ``scope_*``
lines alone.  Wrapper events (``while``, ``conditional``, ``call``) span
their bodies' operations and are left out of every sum.  Extras: the
eight largest generic kinds (``by_kind``, ms a step) and the whole split
by sort, ``named_ms + generic_ms + enclosing_ms + unnamed_ms + wrapper_ms
= ops_ms`` (``enclosing``: what a loop node's own scope keeps, Ouro's
``loop``), beside ``busy_ms``, and ``wrapper_by_kind``: whose wrappers
they are (the older readers' sums hold their kinds' wrappers).  Nothing
where the program gives no table or no ``sort_of`` (an older commit)."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_parts
    return scope_parts.read_generic_share(obs)
