"""Seconds `exp.compile(R, R)` spent tracing the fused program and
lowering it to an MLIR module: the program's own `lower_s` compile counter
as it moved inside its `dfl.compile.lower` span."""


def read(ctx):
    from bench.scopes import program

    p = program(ctx)
    return None if p is None else p["lower_s"]
