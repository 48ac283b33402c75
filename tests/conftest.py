"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see 1 device; multi-device tests run in subprocesses (test_dryrun/test_dht).

JAX's persistent compilation cache is on (benchmarks.common.
enable_compilation_cache: ``$JAX_COMPILATION_CACHE_DIR`` or the checkout's
gitignored ``.jax_cache/``)."""
import numpy as np
import pytest

from benchmarks.common import enable_compilation_cache

enable_compilation_cache()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0xDA5)


def unique_keys(rng, n, lo=1, hi=2**63):
    out = np.unique(rng.integers(lo, hi, size=int(n * 2.2) + 16, dtype=np.uint64))
    assert out.size >= n
    return out[:n]
