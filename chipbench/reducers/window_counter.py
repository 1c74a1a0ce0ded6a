"""A number the driver counted in the window, by name."""


def read(ctx, params):
    return ctx["window"].counters.get(params["counter"])
