"""Imported first by the benchmark's two scripts, before anything
imports JAX: the program on the path, and the compile caches pinned
inside this checkout."""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)       # the program: ethrex_tpu
# JAX reads the variable, and the program then sets no directory of its
# own (utils/jax_cache.py): the caches live in <checkout>/.jax_cache
# whatever the machine's environment says ...
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_REPO, ".jax_cache")
# ... and keep what the first run compiled: a size cap in the machine's
# environment (the chip tool's machines set one of 192 MiB) evicts the
# cold run's entries before the second run can read them (PERF.md, PR 26)
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
