"""Per-layer metric ``scope_other_ms``: device time a traced step in
every scoped operation whose kind no other ``scope_*_ms`` reader names
(``scope_seconds.KINDS``): ``cast``, ``augment``, and the generic scopes of
plain nodes (``fullyconnected``, ``rmsnorm``, ``embedding``, ``pooling``,
...).  ``by_kind`` holds the eight largest.  Nothing where the program
gives no table."""
LAYER = "ops"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_other_ms(obs)
