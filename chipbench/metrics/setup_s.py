"""setup_s: process start to the window's first submit: start-up, data
generation, registration, compiles or cache loads, and warm-up."""


def read(run):
    return run.setup_s
