"""Seconds from the start of the process to the opening of the window:
imports, weights, compiling or loading the steps, deploying, warm-up."""


def read(run):
    return run.setup_s
