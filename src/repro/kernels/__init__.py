# Pallas TPU kernels for the paper's compute hot-spots:
#   kmeans_assign        — blocked n x k distance + argmin (Algorithm 3 / Lloyd)
#   kmeans_assign_update — fused single-pass assign + cluster sums/counts/cost
#                          (one Lloyd iteration = ONE read of X; VKMC scoring
#                          gets cluster_cost/cluster_size from the same pass)
#   leverage             — row-wise quadratic form x_i^T M x_i (Algorithm 2)
#   weighted_gram        — X^T diag(w) X accumulation (coreset ridge solve)
# Each <name>.py holds the pl.pallas_call + BlockSpec; ops.py is the jit'd
# dispatch layer; ref.py the pure-jnp oracles.  All kernels accept leading
# batch dims (folded into the grid by the native pallas vmap rule).
# Every kernel dot runs at Precision.HIGHEST: Mosaic's default for an f32
# dot is one bf16 pass, which left 2.3e-2 relative error in the leverage
# scores on a TPU v5e (see repro.core.sensitivity.HIGHEST).
