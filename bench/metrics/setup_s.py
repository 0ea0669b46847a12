"""Process start to the window's start: drawing and packing the weights,
building the server, and warming every shape the traffic reaches."""


def read(run):
    return run.setup_s
