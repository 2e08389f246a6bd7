"""Backend-compile events (JAX's ``backend_compile_duration``) inside the
traced window, persistent-cache loads included: each is a program lowered
and handed to the backend on the request path.  Every shape is warmed up
first, so a program with a closed set of programs reads 0."""


def read(ctx):
    return float(len(ctx["compiles"]))
