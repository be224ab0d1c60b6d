"""Public jit'd wrappers over the Pallas kernels.

On a TPU backend the kernels compile natively; on CPU they execute under
``interpret=True`` — the kernel bodies run in Python with the exact same
tiling/masking logic, which is what the allclose tests validate against the
``ref.py`` oracles.  Any other backend is refused rather than interpreted:
a kernel that silently runs interpreted on an accelerator hides the device.
Routing to the jnp references is the caller's ``backend="ref"`` choice.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels import kmeans_assign as _ka
from repro.kernels import kmeans_assign_update as _kau
from repro.kernels import leverage as _lev
from repro.kernels import weighted_gram as _wg


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
            f"backend {backend!r} is neither — use backend='ref'")
    return backend == "cpu"


def kmeans_assign(X: jax.Array, C: jax.Array, *, block_n: int = 256) -> Tuple[jax.Array, jax.Array]:
    return _ka.kmeans_assign(X, C, block_n=block_n, interpret=_interpret())


def kmeans_assign_update(
    X: jax.Array, C: jax.Array, w: Optional[jax.Array] = None, *, block_n: int = 256
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused single-pass (assign, d2, csum, wsum, ccost) — ONE read of X.

    Its semantic oracle is ``ref.kmeans_assign_update``, the assignment +
    segment-sum composition (the seed's 3-pass Lloyd data flow).
    """
    return _kau.kmeans_assign_update(X, C, w, block_n=block_n, interpret=_interpret())


def leverage(X: jax.Array, M: jax.Array, *, block_n: int = 512) -> jax.Array:
    return _lev.leverage(X, M, block_n=block_n, interpret=_interpret())


def weighted_gram(X: jax.Array, w: jax.Array, *, block_n: int = 512) -> jax.Array:
    return _wg.weighted_gram(X, w, block_n=block_n, interpret=_interpret())
