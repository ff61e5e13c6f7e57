"""Host seconds of `exp.compile(R, R)`; a load on a compile-cache hit."""


def read(ctx):
    return ctx["setup"].get("compile_s")
