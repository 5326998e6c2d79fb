"""The benchmark's self-tests run on the CPU:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``."""

import os
import sys

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
