"""Compilations and compile-cache loads inside the window (set-up warms
every shape, so any here is a shape it missed)."""


def read(run):
    return run.compiles_in_window
