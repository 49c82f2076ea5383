"""Set-up: process start to the first due request (weights, engine,
compiles or compile-cache loads, warm-up of the cell's shapes)."""


def read(run):
    return run.setup_s
