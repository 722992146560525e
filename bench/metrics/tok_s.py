"""Tokens per second: prompt and generated tokens of every batch that
completed, over the host-clock time from the window's first ``generate``
call to the return of its last batch (which started before the window's
seconds ran out and is counted in full)."""


def read(ctx):
    done = [b for b in ctx.batches if not b.failed]
    tokens = sum(b.B * (b.S + b.gen) for b in done)
    return tokens / (ctx.window[1] - ctx.window[0]) if done else None
