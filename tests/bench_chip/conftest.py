"""The chip benchmark's CPU tests import its modules by name, as
`benchmarks/chip/run.py` does."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
for p in (str(BENCH), str(Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402

CACHE_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def jax_cache_restored():
    """A run points JAX's persistent cache into its checkout; put the
    process's settings back afterwards so later tests compile as before."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    old = {k: jax.config.values[k] for k in CACHE_KEYS}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
