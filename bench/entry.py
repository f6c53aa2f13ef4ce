"""What every entry point of the benchmark (``run.py``, ``sweep.py``,
``calibrate.py``) does first, before numpy or JAX load. Each imports it as
``entry``: a script's own directory leads ``sys.path``.

- one BLAS thread, so the co-located job's parallelism is its scheduler
  tasks' own;
- the checkout and its ``src/`` on the path;
- JAX's compile cache at a fixed path inside the checkout, whatever the
  environment names, so that only the first run of a cell there compiles.
"""

import os
import sys
from pathlib import Path

for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[var] = "1"

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def tpus(chips: int = 1) -> bool:
    """True where JAX finds at least ``chips`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "tpu" and len(devices) >= chips:
        return True
    print(f"bench: needs {chips} TPU chip(s); JAX finds {devices}. "
          f"Nothing run.", file=sys.stderr)
    return False
