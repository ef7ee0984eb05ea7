"""Backend compiles (or loads from the persistent cache) in the window;
set-up should leave none."""


def read(ctx):
    return ctx["compiles_in_window"]
