"""Compile requests (a compile or a persistent-cache load; either is wrong
inside the window) whose wall-clock span overlaps the traced window: the
program's own log of them against the window's bounds on the trace's
clock.  It should read 0."""


def read(ctx):
    from bench.scopes import program

    p = program(ctx)
    return None if p is None else p["window_compiles"]
