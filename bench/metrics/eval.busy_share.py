"""Device self time of the ops whose innermost named scope is `dfl.eval`
(the eval pass and its params probes), over the device's busy time."""

SCOPE = "dfl.eval"


def read(ctx):
    from bench.scopes import busy_share

    return busy_share(ctx, SCOPE)
