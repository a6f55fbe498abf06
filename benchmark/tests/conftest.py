"""The benchmark's own tests run on the CPU, by hand:

    python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of tier-1 (``tests/``). The environment is set before
jax is imported; nothing compiled here is written to the checkout's
compile cache."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
