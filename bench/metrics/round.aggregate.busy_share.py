"""Device self time of the ops whose innermost named scope is
`dfl.aggregate` (flatten, weights, the strategy's update arithmetic and
unflatten; the reduce inside it is `dfl.reduce`'s), over the device's
busy time."""

SCOPE = "dfl.aggregate"


def read(ctx):
    from bench.scopes import busy_share

    return busy_share(ctx, SCOPE)
