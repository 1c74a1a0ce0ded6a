"""Mean of one of the program's registry histograms over the window:
the difference of its ``.sum`` over the difference of its ``.count``
(exact, where its bucket edges do not resolve a median)."""


def read(ctx, params):
    before, after = ctx["registry"]
    name = params["histogram"]
    n = after.get(name + ".count", 0) - before.get(name + ".count", 0)
    if not n:
        return None
    return (after.get(name + ".sum", 0.0)
            - before.get(name + ".sum", 0.0)) / n
