"""Process start to the opening of the window: native build, data from the
seed, session, and one execution of each text of the cell."""


def read(ctx):
    return ctx["setup_s"]
