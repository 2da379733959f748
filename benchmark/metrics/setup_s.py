"""Seconds from the process's start to the window's first due publish:
native build check, boot, table, connections and warm-up."""


def read(ctx):
    return ctx.setup_s()
