"""Programs compiled inside the window, as JAX's own compile events
count them (``benchmark/window.py`` listens from the window's opening to
its close); 0 is expected."""


def read(ctx):
    return float(ctx["window"].compile_events)
