"""Seconds `exp.compile(R, R)` spent in the backend's compile request:
the cache-key hash and the persistent-cache read and load (a real compile
on a miss).  The program's own `load_s` compile counter as it moved inside
its `dfl.compile.load` span."""


def read(ctx):
    from bench.scopes import program

    p = program(ctx)
    return None if p is None else p["load_s"]
