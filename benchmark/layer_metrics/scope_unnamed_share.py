"""Per-layer metric ``scope_unnamed_share``: the share of the first
device's busy time in operations the program's table gives no scope:
layout copies and converts the compiler made itself, and whatever the
step computes outside every scope.  Its extras carry the whole split:
``scoped_ms + unnamed_ms = ops_ms``, beside ``busy_ms``.  Nothing where
the program gives no table."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_unnamed_share(obs)
