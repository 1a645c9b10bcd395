"""Layer: entry points. Seconds JAX spent building or loading programs
during set-up, from its own monitoring events; a warm run's are the loads
from the persistent cache. Moves ``setup_s``."""


def read(run, reduction):
    return run.setup_compile_seconds
