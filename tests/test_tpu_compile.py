"""Compile the fused round kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed beside the CPU backend,
lowers and compiles each kernel for a chip that is described and not
attached.  That catches what interpret mode cannot see (tile alignment of
block shapes, VMEM budget, ops Mosaic does not lower) at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# ragged on purpose: D is not a multiple of any block, L not of 8 (or 16)
P, L, D = 16, 12, 16384 + 200


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache(monkeypatch):
    """The persistent cache cannot read back a chip compile without the
    chip; keep it off around these compiles.  The autotuner times
    candidates by running them, which a described chip cannot do, so it
    takes the largest tile here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "0")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["none", "mask", "laplace"])
def test_round_fold_compiles_for_v5e(one_chip, no_cache, mode, dtype):
    args = [_sds((P, D), dtype, one_chip), _sds((P, L, D), dtype, one_chip)]
    kw = {}
    if mode == "mask":
        kw["seeds"] = _sds((P,), jnp.uint32, one_chip)
    elif mode == "laplace":
        kw["noise"] = _sds((P, L, D), dtype, one_chip)

    def fold(w, grads, **extra):
        return ops.round_fold(w, grads, mu=0.1, bound=1.0, mode=mode,
                              sigma=0.5, backend="pallas", interpret=False,
                              **extra)

    compiled = jax.jit(fold).lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    psi, sq = jax.eval_shape(fold, *args, **kw)
    assert psi.shape == (P, D) and psi.dtype == dtype
    assert sq.shape == (P, L)


@pytest.mark.parametrize("noisy", [False, True], ids=["plain", "noisy"])
def test_graph_combine_compiles_for_v5e(one_chip, no_cache, noisy):
    Pc = 10                                   # not a multiple of 8 either
    args = [_sds((Pc, Pc), jnp.float32, one_chip),
            _sds((Pc, D), jnp.float32, one_chip)]
    if noisy:
        args.append(_sds((Pc, D), jnp.float32, one_chip))

    def combine(*a):
        return ops.graph_combine(*a, backend="pallas", interpret=False)

    compiled = jax.jit(combine).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
