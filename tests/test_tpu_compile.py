"""Ahead-of-time compiles of the four Pallas kernels for a described TPU v5e
chip, at the paper's YearPredictionMSD widths (515,345 rows, 90 features
over T=3 parties, k=10): once for one party and once with all parties
stacked, the form the library calls.

Interpret-mode tests run the kernel bodies in Python and cannot see what
Mosaic refuses (block shapes off the (8, 128) tiling, output layouts XLA
tiles differently); the TPU compiler is installed without a chip, so these
compiles can.  Each asserts the kernel is in the compiled program as a
``tpu_custom_call``.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import kmeans_assign as _ka
from repro.kernels import kmeans_assign_update as _kau
from repro.kernels import leverage as _lev
from repro.kernels import weighted_gram as _wg

N, T, K = 515_345, 3, 10
S = 30              # per-party width, 90 features over 3 parties
S_LABELED = 31      # party T's width with the label column appended


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology, with the persistent
    compilation cache off (an AOT entry cannot be read back without a
    chip) and the TPU compiler's logs off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


# (kernel, operand shapes: one party, operand shapes: all parties stacked)
CASES = {
    "leverage": (
        lambda X, M: _lev.leverage(X, M),
        [(N, S_LABELED), (S_LABELED, S_LABELED)],
        [(T, N, S_LABELED), (T, S_LABELED, S_LABELED)],
    ),
    "weighted_gram": (
        lambda X, w: _wg.weighted_gram(X, w),
        [(N, S_LABELED), (N,)],
        [(T, N, S_LABELED), (T, N)],
    ),
    "kmeans_assign": (
        lambda X, C: _ka.kmeans_assign(X, C),
        [(N, S), (K, S)],
        [(T, N, S), (T, K, S)],
    ),
    "kmeans_assign_update": (
        lambda X, C, w: _kau.kmeans_assign_update(X, C, w),
        [(N, S), (K, S), (N,)],
        [(T, N, S), (T, K, S), (T, N)],
    ),
}


@pytest.mark.parametrize("form", ["one_party", "stacked"])
@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel, form):
    fn, one, stacked = CASES[kernel]
    shapes = one if form == "one_party" else stacked
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
