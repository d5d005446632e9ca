"""setup_s: process start to the window's first commit, as the harness saw
it: compiles, profiling, start-up replication, warm-up and, where the cell
has a fault, the rehearsal of its recovery."""


def read(ctx):
    return ctx.setup_s
