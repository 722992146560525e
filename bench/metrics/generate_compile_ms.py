"""Mean ``Generation.compile_s`` per ``generate`` call of the window, in
ms: the lowering and compiling (or loading from the persistent cache) of
the prefill and decode programs that ``generate`` does on every call.
Read from the program's own timing, over every batch of the window."""


def read(ctx):
    done = [b for b in ctx.batches if not b.failed]
    return 1e3 * sum(b.compile_s for b in done) / len(done) if done else None
