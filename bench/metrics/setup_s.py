"""Process start to the first timed step: building the model from the
seed, calibration, compiling or loading every step shape, warm-up."""


def read(ctx):
    return ctx.setup_s
