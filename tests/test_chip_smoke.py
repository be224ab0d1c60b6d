"""chip_smoke.py on the CPU: every phase's reference checks at n=8,192
(Pallas kernels in interpret mode), and the script's refusal to run, or to
print a result, without a TPU."""

import jax
import numpy as np
import pytest

import chip_smoke

SMALL = chip_smoke.Config(n=8192, m=512, block_size=2048, chunk_blocks=2,
                          svc_rows=4096, svc_inserts=4, svc_m=256)


@pytest.fixture(scope="module")
def data():
    assert chip_smoke.BACKEND == "pallas"
    return chip_smoke.make_data(SMALL)


@pytest.fixture(scope="module")
def vrlr_scores(data):
    return chip_smoke.phase_vrlr(SMALL, data)


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "platform=cpu" in out


def test_phase_vrlr_materialized(vrlr_scores):
    assert vrlr_scores.shape == (SMALL.T, SMALL.n)
    assert np.all(vrlr_scores >= 1.0 / SMALL.n)


def test_phase_vkmc_materialized(data):
    chip_smoke.phase_vkmc(SMALL, data)


def test_phase_pipelined(data, vrlr_scores):
    chip_smoke.phase_pipelined(SMALL, data, vrlr_scores)


def test_phase_service():
    chip_smoke.phase_service(SMALL)


def test_phase_native_rejects_interpreted_kernels(data):
    with pytest.raises(chip_smoke.CheckFailed, match="tpu_custom_call"):
        chip_smoke.phase_native(SMALL, data)


def test_reference_checks_catch_a_wrong_score():
    rng = np.random.default_rng(0)
    f = rng.standard_normal((1, 256, 4))
    ref = chip_smoke.ref_vrlr_scores(f, (4,))
    np.testing.assert_allclose(ref.sum(), 4.0 + 1.0, rtol=1e-12)
    bad = ref * (1.0 + 2 * chip_smoke.TOL_ROW)
    assert chip_smoke.max_rel(bad, ref) > chip_smoke.TOL_ROW
