"""``compilewatch.total_compiles()`` at the window's end less its start
(backend compilations and cache reads alike); must be 0."""


def read(ctx, params):
    return ctx["compiles"]
