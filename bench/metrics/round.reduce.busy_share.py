"""Device self time of the ops whose innermost named scope is `dfl.reduce`
(the neighbour gather, the pad and the `segment_avg` kernel together),
over the device's busy time."""

SCOPE = "dfl.reduce"


def read(ctx):
    from bench.scopes import busy_share

    return busy_share(ctx, SCOPE)
