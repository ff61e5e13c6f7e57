"""Host seconds of `Experiment(...)`: padding, device transfer, init."""


def read(ctx):
    return ctx["setup"].get("init_s")
