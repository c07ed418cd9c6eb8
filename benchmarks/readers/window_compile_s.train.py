"""Seconds the program's dispatch ledger charged to compiling inside the window; should read 0."""


def read(ctx):
    return ctx["window"].get("window_compile_s")
