"""GFL repro package.

One package-wide PRNG policy: the partitionable threefry implementation.
With the legacy non-partitionable threefry, the values drawn for a
tensor-parallel-sharded leaf can depend on the downstream program's
sharding, so the same key yields DIFFERENT privacy noise under dense vs
rotate/sparse mesh combine — breaking cross-impl noise reproducibility and
making results depend on which repro modules happen to be imported.
Setting it here (the root of every repro import path) makes the choice
deterministic for the whole process; an explicit JAX_THREEFRY_PARTITIONABLE
environment setting wins.
"""
import os as _os

import jax as _jax

if "JAX_THREEFRY_PARTITIONABLE" not in _os.environ:
    _jax.config.update("jax_threefry_partitionable", True)

#: environment override for the package-wide base seed (see rng_key)
SEED_ENV = "REPRO_SEED"

#: the persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset
CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Entry points call this (never on import, so tests keep JAX's default).
    A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and is left
    alone.  Otherwise the cache is :data:`CACHE_DIR`, one fixed path in
    the checkout: a directory that moved from run to run would never hit.
    """
    path = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    _jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def rng_key(seed=None) -> "_jax.Array":
    """The approved seed factory (gflint GFL001).

    Launchers and demos must not hard-code ``PRNGKey(0)`` at the call
    site — a sweep that forgets to thread its seed then silently shares
    randomness across runs.  ``rng_key()`` draws the base key from one
    place: an explicit ``seed`` argument wins, else the ``REPRO_SEED``
    environment variable, else 0 (bit-identical to the historical
    ``PRNGKey(0)`` default, so existing goldens are unchanged).
    Derive per-use keys with ``jax.random.fold_in``/``split`` as usual.
    """
    if seed is None:
        seed = int(_os.environ.get(SEED_ENV, "0"))
    return _jax.random.PRNGKey(seed)
