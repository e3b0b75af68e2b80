"""Host seconds of ``Experiment.precompute()``: the SAFA event process
(versions, crash draws, CFCFM) and the schedule it lowers to."""


def read(ctx):
    return ctx.precompute_s
