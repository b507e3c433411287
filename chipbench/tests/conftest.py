"""The benchmark's own tests run on the CPU.  The variable must be set
before jax loads."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
