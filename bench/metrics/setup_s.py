"""Set-up time: from the start of the process to the start of the window
(imports, config checks, weights, prompt pool, and one ``generate`` call
for each shape of the traffic, which compiles on a cold cache)."""


def read(ctx):
    return ctx.setup_s
