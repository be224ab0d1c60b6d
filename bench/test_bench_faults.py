"""A sound comparison calls broken outputs not correct: the bfloat16
control and faults planted in the timed path, each in a whole run of a
tiny cell on the CPU (the chip check skipped)."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench import control


def _alter_index(orig):
    def dis(key, scores, m, *a, **kw):
        p = orig(key, scores, m, *a, **kw)
        return p._replace(indices=p.indices.at[0].set((p.indices[0] + 1) % scores.shape[1]))
    return dis


def _alter_weight(orig):
    def dis(key, scores, m, *a, **kw):
        p = orig(key, scores, m, *a, **kw)
        return p._replace(weights=p.weights.at[0].multiply(1.001))
    return dis


def _alter_streamed_index(orig):
    def dis(scorer, m, **kw):
        p = orig(scorer, m, **kw)
        return p._replace(indices=p.indices.at[0].set((p.indices[0] + 1) % scorer.n))
    return dis


def _alter_streamed_weight(orig):
    def dis(scorer, m, **kw):
        p = orig(scorer, m, **kw)
        return p._replace(weights=p.weights.at[0].multiply(1.001))
    return dis


def _half_rows_streamed_gram(orig):
    """The streamed Gram over the first half of each block's rows, doubled."""
    def gram(G, chunk, nvalids, **kw):
        half = chunk.at[:, :, chunk.shape[2] // 2:].set(0)
        return G + 2.0 * (orig(jnp.zeros_like(G), half, nvalids, **kw))
    return gram


def _half_gram_scores(orig):
    """Leverage against the Gram of the first half of the rows, doubled."""
    def scores(blocks, *a, **kw):
        f = blocks.astype(jnp.float32)
        half = f[:, : f.shape[1] // 2]
        G = 2.0 * jnp.einsum("tns,tnu->tsu", half, half)
        M = jnp.linalg.pinv(G)
        lev = jnp.einsum("tns,tsr,tnr->tn", f, M, f)
        return jnp.clip(lev, 0.0, 1.0) + 1.0 / f.shape[1]
    return scores


FAULTS = [
    ("ridge.mat", "repro.core.api", "dis_plan_full", _alter_index),
    ("ridge.mat", "repro.core.api", "dis_plan_full", _alter_weight),
    ("ridge.mat", "repro.core.api", "vrlr_scores_stacked", _half_gram_scores),
    ("ridge.pipe", "repro.core.streaming", "dis_plan_streamed_batched",
     _alter_streamed_index),
    ("ridge.pipe", "repro.core.streaming", "dis_plan_streamed_batched",
     _alter_streamed_weight),
    ("ridge.pipe", "repro.core.streaming", "_gram_chunk", _half_rows_streamed_gram),
]


@pytest.mark.parametrize("workload,module,attr,fault", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}" for w, _, _, f in FAULTS])
def test_planted_fault_is_not_correct(run_cell, monkeypatch, workload, module, attr, fault):
    import importlib

    mod = importlib.import_module(module)
    res, _ = run_cell(workload)
    assert res["correct"] is True
    monkeypatch.setattr(mod, attr, fault(getattr(mod, attr)))
    res, _ = run_cell(workload)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


@pytest.mark.parametrize("workload", ["ridge.mat", "ridge.pipe"])
def test_bfloat16_control_is_not_correct(run_cell, workload):
    res, _ = run_cell(workload, hook=control.bf16_parties)
    assert res["correct"] is False
    assert res["checks"]["weight_rel"]["value"] > res["checks"]["weight_rel"]["limit"]
