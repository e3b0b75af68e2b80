"""Host milliseconds of one ``evaluate`` at a segment boundary (mean over
the window's boundaries)."""


def read(ctx):
    if not ctx.eval_s:
        return None
    return 1000.0 * sum(ctx.eval_s) / len(ctx.eval_s)
