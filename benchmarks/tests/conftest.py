"""The benchmark's own tests run here on the CPU, at tests/tiny's sizes:

    python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 suite (`pytest tests/`).
"""

import os
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
# A cache of this machine's CPU programs, inside the checkout but apart
# from the chip's (.cache/jax): the two only make noise for each other.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    str(Path(__file__).resolve().parents[2] / ".cache" / "jax-cpu-tests"))
