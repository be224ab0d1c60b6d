"""Operations and bytes of the fused Lloyd kernel (``kmeans_assign_update``)
in a k-means build, counted from the deployment's unpadded shapes.

One pass over party j's (n, d_j) rows computes every row's distances to
the k centres (2 n k d_j) and folds the rows into the per-cluster sums
through a one-hot product (2 n k d_j); it reads X_j once and writes each
row's assignment and squared distance.  The centres and the per-cluster
outputs (k d_j values each) are left out: at k = 10 they are under 0.1%
of the bytes.  A build makes ``local_iters`` Lloyd passes and one scoring
pass per party.  float32 data (4 bytes a value).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from bench.data import party_widths
from bench.work import F32


def assign_update(n: int, widths: Sequence[int], k: int) -> Tuple[float, float]:
    """(flops, bytes) of one pass over every party."""
    flops = sum(4.0 * n * k * w for w in widths)
    nbytes = sum(F32 * (n * w + 2 * n) for w in widths)
    return flops, nbytes


def lloyd_passes(config: Dict) -> int:
    """Kernel passes over every party's rows in one build."""
    return int(config["local_iters"]) + 1


def build(config: Dict) -> Tuple[float, float]:
    """(flops, bytes) of one build's fused Lloyd kernel work."""
    if config["task"] != "vkmc":
        raise ValueError(f"no k-means work for task {config['task']!r}")
    widths = party_widths(int(config["d"]), int(config["T"]))
    flops, nbytes = assign_update(int(config["n"]), widths, int(config["k"]))
    p = lloyd_passes(config)
    return p * flops, p * nbytes
