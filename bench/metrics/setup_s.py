"""Set-up: process start to the first due request, compiles included."""


def read(run):
    return run.setup_s
