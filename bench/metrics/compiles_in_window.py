"""compiles_in_window: backend compiles inside the measured window, as
JAX's monitoring events count them (``harness.CompileCounter``; a read of
the persistent cache counts too). Every program shape the window runs is
warmed up in set-up, so a compile here is a shape the warm-up missed, and
its seconds land in the window's runs."""


def read(rec):
    return rec.compiles_in_window
