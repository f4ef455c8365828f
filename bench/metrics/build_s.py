"""Seconds of the set-up span ``build`` (host clock; see ``systems/__init__.py``
and ``harness.run_cell``)."""


def read(ctx):
    return ctx.spans.get("build")
