"""Process start to the window opening: imports, TPU start-up, weights,
the engine, warm-up (with compiles or cache loads) and the agent."""


def read(run):
    return run.setup_s
