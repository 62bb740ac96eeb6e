# NOTE: do not import .dryrun here — it sets XLA_FLAGS at import time and
# must only be imported as the top-level entry point of its own process.
from .mesh import make_mesh  # noqa
