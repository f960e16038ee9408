"""setup_s (host clock): from the command's start to the window's first
dispatch: world, program objects, compiles or cache loads, warm-up."""


def read(run):
    return run.outcome.setup_s
