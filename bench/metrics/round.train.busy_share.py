"""Device self time of the ops whose innermost named scope is `dfl.train`
(local training: batch gather, forward, backward, optimizer), over the
device's busy time."""

SCOPE = "dfl.train"


def read(ctx):
    from bench.scopes import busy_share

    return busy_share(ctx, SCOPE)
