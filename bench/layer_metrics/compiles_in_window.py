"""Programs JAX prepared inside the window (compiled, or loaded from the
persistent cache), counted from JAX's own compile events."""


def read(ctx):
    return ctx["window"].compiles_in_window
