"""End-to-end coreset quality for VKMC (Algorithm 3) + DistDim baseline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommLedger,
    CoresetPipeline,
    CoresetSpec,
    VFLDataset,
    build_coresets_batched,
    build_uniform_coreset,
    build_vkmc_coreset,
    distdim,
    kmeans,
    kmeans_cost,
    vkmc_coreset_ratio,
)
from repro.core.vkmc import kmeans_plusplus
from repro.data.synthetic import correlated_vfl_data


def _clustered(key, n=3000, d=12, T=3, k=5, rho=0.8):
    X = correlated_vfl_data(key, n, d, T, cross_correlation=rho, k_clusters=k)
    return VFLDataset.from_dense(X, None, T=T)


def test_vkmc_coreset_solution_quality():
    k = 5
    ds = _clustered(jax.random.PRNGKey(0), k=k)
    cs = build_vkmc_coreset(jax.random.PRNGKey(1), ds, k=k, m=500)
    XS, _, w = cs.materialize(ds)
    cent_full = kmeans(jax.random.PRNGKey(2), ds.full(), k)
    cent_cs = kmeans(jax.random.PRNGKey(2), XS, k, w)
    c_full = float(kmeans_cost(ds.full(), cent_full))
    c_cs = float(kmeans_cost(ds.full(), cent_cs))
    assert c_cs <= 1.15 * c_full, (c_cs, c_full)


def test_vkmc_coreset_epsilon_over_probe_centers():
    k = 4
    ds = _clustered(jax.random.PRNGKey(3), n=1500, k=k)
    cs = build_vkmc_coreset(jax.random.PRNGKey(4), ds, k=k, m=600)
    C_probe = jax.random.normal(jax.random.PRNGKey(5), (10, k, ds.d)) * 2.0
    eps = float(vkmc_coreset_ratio(ds, cs, C_probe))
    assert eps < 0.5, eps


def test_vkmc_coreset_beats_uniform():
    """C-KMEANS++ is no worse than U-KMEANS++ at matched budget (Table 1).

    The seed version of this test flaked: it averaged ONE downstream Lloyd
    solve per construction seed, and weighted Lloyd is local-optimum
    roulette with a heavy upper tail (~2-3x cost basins) — any single draw
    can land badly regardless of coreset fidelity, and a mean over 6 draws
    is dominated by that basin luck.  Theorem 5.1 bounds the coreset's COST
    RATIO, not which basin the downstream solver picks, so the statistic
    here is basin-robust: all construction seeds are built in one compiled
    ``build_coresets_batched`` call, each coreset is solved with best-of-3
    downstream restarts (standard k-means practice), and the MEDIAN over
    the fixed 12-seed batch is compared within a 3% margin.
    """
    k, m, R = 6, 120, 12
    ds = _clustered(jax.random.PRNGKey(6), n=4000, k=k, rho=0.9)
    Xf = ds.full()
    grid_c = build_coresets_batched("vkmc", ds, [m], key=jax.random.PRNGKey(100),
                                    num_seeds=R, backend="ref", k=k)
    grid_u = build_coresets_batched("uniform", ds, [m], key=jax.random.PRNGKey(200),
                                    num_seeds=R)

    def median_cost(grid):
        costs = []
        for r in range(R):
            cs = grid.coreset(r, 0)
            XS, w = Xf[cs.indices], cs.weights
            costs.append(min(
                float(kmeans_cost(Xf, kmeans(jax.random.PRNGKey(7 + t), XS, k, w,
                                             use_kernel=False),
                                  use_kernel=False))
                for t in range(3)))
        return float(np.median(costs))

    cs_c, un_c = median_cost(grid_c), median_cost(grid_u)
    assert cs_c <= un_c * 1.03, (cs_c, un_c)


def test_distdim_runs_and_costs_linear_comm():
    k = 4
    ds = _clustered(jax.random.PRNGKey(8), n=800, k=k)
    led = CommLedger()
    cent = distdim(jax.random.PRNGKey(9), ds, k, ledger=led)
    assert cent.shape == (k, ds.d)
    # Ding et al. cost: assignments n per party + local centers
    assert led.total >= ds.n * ds.T
    c = float(kmeans_cost(ds.full(), cent))
    c_central = float(kmeans_cost(ds.full(), kmeans(jax.random.PRNGKey(10), ds.full(), k)))
    assert c <= 3.0 * c_central       # constant-approx regime


def test_coreset_comm_much_smaller_than_distdim():
    k = 4
    ds = _clustered(jax.random.PRNGKey(11), n=5000, k=k)
    led_cs, led_dd = CommLedger(), CommLedger()
    build_vkmc_coreset(jax.random.PRNGKey(12), ds, k=k, m=200, ledger=led_cs)
    distdim(jax.random.PRNGKey(13), ds, k, ledger=led_dd)
    assert led_cs.total < led_dd.total / 5


def _dot_precisions(jaxpr):
    """``precision`` of every ``dot_general`` in a jaxpr and its sub-jaxprs."""
    from jax.extend import core

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, core.ClosedJaxpr):
                    out += _dot_precisions(sub.jaxpr)
                elif isinstance(sub, core.Jaxpr):
                    out += _dot_precisions(sub)
    return out


def test_kmeans_plusplus_distances_run_at_highest_precision():
    """The seeding's D^2 distances: at the TPU's DEFAULT precision (one
    bf16 pass) a draw at a near-tie can take another row than float64."""
    X = jax.random.normal(jax.random.PRNGKey(0), (257, 9))
    jaxpr = jax.make_jaxpr(lambda key, X: kmeans_plusplus(key, X, 5))(
        jax.random.PRNGKey(1), X)
    precisions = _dot_precisions(jaxpr.jaxpr)
    assert precisions
    highest = jax.lax.Precision.HIGHEST
    assert all(p is not None and all(q == highest for q in p) for p in precisions), \
        precisions


@pytest.mark.parametrize("shape", [(257, 9), (1031, 4)])
@pytest.mark.parametrize("weighted", [False, True])
def test_jitted_kmeans_plusplus_equals_the_eager_body(shape, weighted):
    """The compiled seeding draws the rows the op-by-op body draws, bit for
    bit: alone, weighted or not, and vmapped over three parties as
    ``vkmc_scores`` calls it."""
    X = jax.random.normal(jax.random.PRNGKey(shape[0]), shape) * jnp.linspace(0.5, 2.0, shape[1])
    w = (jax.random.uniform(jax.random.PRNGKey(7), shape[:1]) * 3.0).at[::5].set(0.0) \
        if weighted else None
    key = jax.random.PRNGKey(31)
    subs = jax.random.split(jax.random.PRNGKey(32), 3)
    blocks = jnp.stack([X, X[::-1], X * 0.5])

    def run():
        one = kmeans_plusplus(key, X, 6, w)
        three = jax.vmap(lambda s, B: kmeans_plusplus(s, B, 6, w))(subs, blocks)
        return np.asarray(one), np.asarray(three)

    one, three = run()
    with jax.disable_jit():
        one_eager, three_eager = run()
    assert one.shape == (6, shape[1]) and three.shape == (3, 6, shape[1])
    np.testing.assert_array_equal(one.view(np.uint32), one_eager.view(np.uint32))
    np.testing.assert_array_equal(three.view(np.uint32), three_eager.view(np.uint32))


def test_vkmc_materialized_draw_is_unchanged_on_the_cpu():
    """A recorded build: the spans around seeding, Lloyd and scoring and
    the HIGHEST seeding dot change no draw and no weight on the CPU, where
    DEFAULT is already full float32."""
    X = jax.random.normal(jax.random.PRNGKey(21), (2000, 12)) * jnp.linspace(0.5, 2.0, 12)
    ds = VFLDataset([X[:, 0:4], X[:, 4:8], X[:, 8:12]], None)
    spec = CoresetSpec(task="vkmc", budgets=24, engine="materialized", backend="ref",
                       params={"k": 5, "alpha": 2.0, "local_iters": 4})
    pipe = CoresetPipeline(ds)
    led = CommLedger()
    cs = pipe.build(pipe.plan(spec), key=jax.random.PRNGKey(5), ledger=led)
    S, w = np.asarray(cs.indices), np.asarray(cs.weights)
    assert S.tolist() == [1357, 1607, 1906, 1196, 289, 114, 1589, 1738, 1723, 1910, 589,
                          157, 1351, 222, 1616, 27, 606, 954, 961, 1018, 1045, 1984, 385,
                          1140]
    assert w.view(np.uint32).tolist() == [
        1118534984, 1117660448, 1118121366, 1117995357, 1117870634, 1119293047,
        1116535405, 1118795117, 1118090048, 1118315365, 1117534650, 1117422857,
        1118352385, 1116730821, 1119128305, 1118647721, 1118611351, 1116944168,
        1117462232, 1118582738, 1118658953, 1117260517, 1118234917, 1119646229]
    assert led.total == 174
