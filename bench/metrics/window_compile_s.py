"""window_compile_s: seconds JAX reported (through ``jax.monitoring``) for
getting executables while the window ran: a backend compile, or a load
from the persistent compile cache, which is where every program lands
once compiled. Every shape is warmed up in set-up, so anything here is the
program building a program again per graph."""


def read(run):
    return run.compile_s
