"""Operations and bytes that each score kernel's pass needs, counted from
the unpadded shapes of the deployment.

The counts are the algorithm's, not the kernel's: they do not change with
how a kernel pads lanes or tiles rows, so a kernel that packs or is
replaced is held to the same numerator.  float32 data (4 bytes a value).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from bench.data import party_widths

F32 = 4


def leverage(n: int, widths: Sequence[int]) -> Tuple[float, float]:
    """Row quadratic forms x_i^T M_j x_i over every party: read X_j and M_j,
    write one score per row.  (flops, bytes)."""
    flops = sum(n * (2.0 * w * w + 2.0 * w) for w in widths)
    nbytes = sum(F32 * (n * w + w * w + n) for w in widths)
    return flops, nbytes


def score_pass(config: Dict) -> Tuple[float, float]:
    """(flops, bytes) of one build's kernel work: one leverage pass for
    ridge (Algorithm 2)."""
    if config["task"] != "vrlr":
        raise ValueError(f"no work function for task {config['task']!r}")
    n, T = int(config["n"]), int(config["T"])
    widths = party_widths(int(config["d"]), T)
    widths[-1] += 1                           # party T's label column
    return leverage(n, widths)


def least_seconds(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The roofline's least time and which of the two bounds it."""
    t_c = flops / float(peaks["flops_per_s"])
    t_m = nbytes / float(peaks["hbm_bytes_per_s"])
    return (t_m, "hbm") if t_m >= t_c else (t_c, "flops")
