"""Seconds from the process's start to the window's: imports, planning,
engine build and the warm-up run, which compiles or loads the programs."""


def read(ctx):
    return ctx.setup_s
