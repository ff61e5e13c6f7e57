"""Device self time of the ops whose innermost named scope is
`dfl.exchange` (trigger, encode, wire, decode and delivery bookkeeping),
over the device's busy time."""

SCOPE = "dfl.exchange"


def read(ctx):
    from bench.scopes import busy_share

    return busy_share(ctx, SCOPE)
