"""Backend compiles inside the measured window (JAX monitoring); 0 when
set-up warmed every shape the window uses."""


def read(ctx):
    return ctx["compiles"]
