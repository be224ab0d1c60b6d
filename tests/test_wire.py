"""The compressed wire: codecs, bit billing, and the planner codec axis.

Two layers:

* plain pytest — seeded round-trip/bit-contract checks over a fixed shape
  grid, spec/plan validation, and the raw_fp32 bit-identity pins across
  all four engines (these always run);
* hypothesis properties (skipped where hypothesis is absent, the
  container default) — ``decode(encode(x))`` within the documented
  tolerance and ``wire_bits == 8 * len(encode(x))`` for arbitrary
  payloads, the contract ``benchmarks/compression.py`` reconciles
  against receipts.
"""

import jax
import numpy as np
import pytest

from repro.core import (
    CommLedger,
    CommSchedule,
    CoresetPipeline,
    CoresetSpec,
    FaultPlan,
    Transport,
    VFLDataset,
    build_coresets_batched,
    compile_plan,
)
from repro.core.plan import PLAN_KEY_FIELDS
from repro.core.wire import (
    CODEC_LADDER,
    SPEC_CODECS,
    UNIT_BITS,
    WIRE_CODECS,
    WirePayload,
    choose_codec,
    encode_payloads,
    fmt_bits,
    get_codec,
    predict_dis_bits,
    predict_uniform_bits,
)

#: shape grid covering the seams: empty, scalar-ish, one int8 block,
#: one-past-a-block, multi-d, and a long row
FLOAT_SHAPES = [(0,), (1,), (5,), (64,), (65,), (4, 7), (300,)]


def _dataset(key, n=400, d=10, T=3):
    X = jax.random.normal(key, (n, d))
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    return VFLDataset.from_dense(X, y, T=T)


# -- codec contract (seeded grid; always runs) -------------------------------

@pytest.mark.parametrize("name", sorted(WIRE_CODECS))
def test_float_roundtrip_within_documented_tolerance(name):
    c = get_codec(name)
    rng = np.random.default_rng(7)
    for shape in FLOAT_SHAPES:
        x = (10.0 * rng.standard_normal(shape)).astype(np.float32)
        blob = c.encode(x)
        out = c.decode(blob, x.shape, x.dtype)
        assert out.shape == x.shape and out.dtype == np.float32
        if c.lossless:
            np.testing.assert_array_equal(out, x)
        else:
            tol = c.tolerance * (float(np.max(np.abs(x))) if x.size else 0.0)
            assert float(np.max(np.abs(out - x), initial=0.0)) <= tol
        # float payloads are shape-determined under every codec: the
        # contract the ledger bills by is EXACT, not a bound
        assert c.bits_exact(np.float32)
        assert 8 * len(blob) == c.wire_bits(shape, "float32")


@pytest.mark.parametrize("name", sorted(WIRE_CODECS))
def test_int_payloads_exact_under_every_codec(name):
    c = get_codec(name)
    rng = np.random.default_rng(11)
    for arr in (np.sort(rng.integers(0, 10**6, 200)).astype(np.int32),
                np.array([], np.int32),
                np.array([0, -5, 2**31 - 1, -2**31], np.int32)):
        blob = c.encode(arr)
        out = c.decode(blob, arr.shape, arr.dtype)
        np.testing.assert_array_equal(out, arr)
        assert c.exact_for(arr.dtype)
        if c.bits_exact(arr.dtype):
            assert 8 * len(blob) == c.wire_bits(arr.shape, "int32")
        else:  # varint: measured never exceeds the certified bound
            assert 8 * len(blob) <= c.wire_bits(arr.shape, "int32")


def test_quantization_errors_are_really_bounded_not_zero():
    # the lossy codecs must actually lose bits on a generic payload —
    # otherwise the tolerance contract is vacuous
    rng = np.random.default_rng(3)
    x = rng.standard_normal(257).astype(np.float32)
    for name in ("fp16", "int8_blockscale"):
        c = get_codec(name)
        out = c.decode(c.encode(x), x.shape, x.dtype)
        assert not np.array_equal(out, x)
        assert not c.lossless and not c.exact_for(np.float32)


def test_nonfinite_and_constant_blocks_survive_encoding():
    c = get_codec("int8_blockscale")
    x = np.zeros(130, np.float32)
    np.testing.assert_array_equal(c.decode(c.encode(x), x.shape, x.dtype), x)
    x[5] = np.inf
    out = c.decode(c.encode(x), x.shape, x.dtype)
    assert np.all(np.isfinite(out))


def test_get_codec_unknown_name():
    with pytest.raises(ValueError, match="unknown wire codec"):
        get_codec("gzip")


def test_fmt_bits_units():
    assert fmt_bits(100) == "100b"
    assert fmt_bits(8 * 2048) == "2.00KiB"
    assert fmt_bits(8 * 3 * (1 << 20)) == "3.00MiB"


def test_wire_payload_of_and_measured():
    p = WirePayload.of((100,), "float32", "fp16")
    assert p.bits == get_codec("fp16").wire_bits((100,), "float32")
    m = WirePayload.measured((100,), "int32", "delta_varint", 816)
    assert m.bits == 816
    with pytest.raises(ValueError, match="negative wire bits"):
        WirePayload((4,), "float32", "raw_fp32", -1)


def test_encode_payloads_bits_match_blobs():
    rng = np.random.default_rng(0)
    payloads = {j: np.sort(rng.integers(0, 5000, 50)).astype(np.int32)
                for j in range(3)}
    blobs, bits = encode_payloads("delta_varint", payloads)
    assert bits == {j: 8 * len(b) for j, b in blobs.items()}


# -- budget walk -------------------------------------------------------------

def test_choose_codec_walks_the_ladder_fidelity_first():
    bits = {"raw_fp32": 1000, "fp16": 600, "int8_blockscale": 300}
    assert choose_codec("auto", None, bits) == ("raw_fp32", False, "")
    name, exceeded, note = choose_codec("auto", 700, bits)
    assert (name, exceeded) == ("fp16", False) and "fp16" in note
    name, exceeded, note = choose_codec("auto", 100, bits)
    assert (name, exceeded) == ("int8_blockscale", True)
    assert "unmeetable" in note
    name, exceeded, note = choose_codec("fp16", 100, bits)
    assert (name, exceeded) == ("fp16", True) and "exceeds" in note


def test_predict_dis_bits_is_the_per_codec_wire_sum():
    T, m, cells = 3, 64, 1024
    for name in CODEC_LADDER:
        c = get_codec(name)
        want = (T * (c.wire_bits((cells,), "float32") + UNIT_BITS)
                + c.wire_bits((m,), "int32") + 2 * T * m * UNIT_BITS)
        assert predict_dis_bits(T, m, cells, name) == want
    assert predict_uniform_bits(T, m) == T * m * UNIT_BITS


# -- spec / plan axis --------------------------------------------------------

def test_spec_codec_validation():
    for bad in ("gzip", "delta_varint"):  # not a spec-selectable table format
        with pytest.raises(ValueError):
            CoresetSpec(task="vrlr", budgets=32, codec=bad)
    with pytest.raises(ValueError, match="batched"):
        CoresetSpec(task="vrlr", budgets=32, codec="int8_blockscale",
                    engine="batched")
    with pytest.raises(ValueError, match="comm_budget_bits"):
        CoresetSpec(task="vrlr", budgets=32, comm_budget_bits=0)
    assert "codec" in PLAN_KEY_FIELDS and "comm_budget_bits" in PLAN_KEY_FIELDS


def test_plan_predicts_bits_and_resolves_auto_codec():
    ds = _dataset(jax.random.PRNGKey(0), n=1024)
    spec = CoresetSpec(task="vrlr", budgets=64, engine="materialized",
                       backend="ref")
    plan = compile_plan(spec, ds)
    assert plan.codec == "raw_fp32"
    assert plan.predicted_wire_bits == predict_dis_bits(ds.T, 64, ds.n,
                                                        "raw_fp32")
    assert "on the wire" in plan.describe()

    tight = predict_dis_bits(ds.T, 64, ds.n, "fp16")
    spec2 = CoresetSpec(task="vrlr", budgets=64, engine="materialized",
                        backend="ref", codec="auto", comm_budget_bits=tight)
    plan2 = compile_plan(spec2, ds)
    assert plan2.codec == "fp16" and not plan2.comm_budget_exceeded
    assert plan2.predicted_wire_bits == tight
    assert "comm budget" in plan2.describe()

    spec3 = CoresetSpec(task="vrlr", budgets=64, engine="materialized",
                        backend="ref", codec="auto", comm_budget_bits=1)
    plan3 = compile_plan(spec3, ds)
    assert plan3.codec == "int8_blockscale" and plan3.comm_budget_exceeded


def test_ledger_bits_column_and_summary():
    led = CommLedger()
    led.party_to_server("x/table", 0, 4, 4096)
    led.party_to_server("x/scalar", 1, 1)       # defaults to UNIT_BITS
    assert led.total_bits == 4096 + UNIT_BITS
    assert led.by_tag(bits=True) == {"x/table": 4096, "x/scalar": UNIT_BITS}
    assert "on the wire" in led.summary()


def test_schedule_payload_bits():
    p = WirePayload.of((500,), "float32", "raw_fp32")
    sched = CommSchedule.dis(3, 16, counts=[16, 0, 0], round1_payload=p)
    # G_j ops bill the table row; every other op stays at UNIT_BITS/unit
    assert sched.total_bits == (3 * (p.bits + UNIT_BITS)
                                + UNIT_BITS * (16 + 2 * 3 * 16))


# -- raw_fp32 bit-identity pins across the engines ---------------------------

@pytest.mark.parametrize("engine", ["materialized", "streamed", "pipelined"])
def test_raw_bits_reconcile_across_engines(engine):
    ds = _dataset(jax.random.PRNGKey(2), n=600)
    spec = CoresetSpec(task="vrlr", budgets=48, engine=engine, backend="ref",
                       block_size=128)
    pipe = CoresetPipeline(ds)
    plan = pipe.plan(spec)
    led0, led1 = CommLedger(), CommLedger()
    key = jax.random.PRNGKey(3)
    cs0 = pipe.build(spec, key=key, ledger=led0)
    cs1 = pipe.build(spec, key=key, ledger=led1,
                     transport=Transport(FaultPlan.none()))
    np.testing.assert_array_equal(np.asarray(cs0.indices),
                                  np.asarray(cs1.indices))
    np.testing.assert_array_equal(np.asarray(cs0.weights),
                                  np.asarray(cs1.weights))
    assert led0.by_tag() == led1.by_tag()
    assert led0.by_tag(bits=True) == led1.by_tag(bits=True)
    for cs, led in ((cs0, led0), (cs1, led1)):
        assert cs.comm_bits == led.total_bits == plan.predicted_wire_bits
        assert cs.comm_units == led.total


def test_raw_bits_reconcile_batched():
    ds = _dataset(jax.random.PRNGKey(4), n=300)
    grid = build_coresets_batched("vrlr", ds, [32], key=jax.random.PRNGKey(5),
                                  backend="ref")
    led = CommLedger()
    cs = grid.coreset(0, 0, ledger=led)
    assert cs.comm_bits == led.total_bits
    assert led.by_tag(bits=True)["dis/round1/G_j"] == ds.T * 32 * ds.n


def test_lossy_codec_requires_a_transport():
    ds = _dataset(jax.random.PRNGKey(6), n=200)
    spec = CoresetSpec(task="vrlr", budgets=16, engine="materialized",
                       backend="ref", codec="fp16")
    with pytest.raises(ValueError, match="transport"):
        CoresetPipeline(ds).build(spec, key=jax.random.PRNGKey(7))
    cs = CoresetPipeline(ds).build(spec, key=jax.random.PRNGKey(7),
                                   ledger=(led := CommLedger()),
                                   transport=Transport(FaultPlan.none()))
    assert cs.comm_bits == led.total_bits
    assert (led.by_tag(bits=True)["dis/round1/G_j"]
            == ds.T * get_codec("fp16").wire_bits((ds.n,), "float32"))


def test_compressed_build_bills_fewer_bits_than_raw():
    ds = _dataset(jax.random.PRNGKey(8), n=2048)
    key = jax.random.PRNGKey(9)
    bills = {}
    for name in ("raw_fp32", "int8_blockscale"):
        spec = CoresetSpec(task="vrlr", budgets=64, engine="materialized",
                           backend="ref", codec=name)
        led = CommLedger()
        CoresetPipeline(ds).build(spec, key=key, ledger=led,
                                  transport=Transport(FaultPlan.none()))
        bills[name] = led.total_bits
    assert bills["int8_blockscale"] < bills["raw_fp32"]


# -- hypothesis properties (skipped without hypothesis) ----------------------

def test_property_roundtrip_and_packed_bits():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(name=st.sampled_from(sorted(WIRE_CODECS)),
           data=st.data(),
           shape=st.one_of(
               st.integers(0, 300).map(lambda n: (n,)),
               st.tuples(st.integers(1, 12), st.integers(1, 12))))
    def prop(name, data, shape):
        c = get_codec(name)
        size = int(np.prod(shape))
        vals = data.draw(st.lists(
            st.floats(-1e6, 1e6, width=32), min_size=size, max_size=size))
        x = np.asarray(vals, np.float32).reshape(shape)
        blob = c.encode(x)
        assert 8 * len(blob) == c.wire_bits(shape, "float32")
        out = c.decode(blob, shape, np.float32)
        if c.lossless:
            np.testing.assert_array_equal(out, x)
        else:
            tol = c.tolerance * (float(np.max(np.abs(x))) if size else 0.0)
            # documented bound plus fp dust from the scale multiply
            assert float(np.max(np.abs(out - x), initial=0.0)) <= tol * (1 + 1e-5)
    prop()


def test_property_varint_ints_exact_and_bounded():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(name=st.sampled_from(sorted(WIRE_CODECS)),
           vals=st.lists(st.integers(-2**31, 2**31 - 1), max_size=200))
    def prop(name, vals):
        c = get_codec(name)
        x = np.asarray(vals, np.int32)
        blob = c.encode(x)
        np.testing.assert_array_equal(c.decode(blob, x.shape, x.dtype), x)
        if c.bits_exact(x.dtype):
            assert 8 * len(blob) == c.wire_bits(x.shape, "int32")
        else:
            assert 8 * len(blob) <= c.wire_bits(x.shape, "int32")
    prop()
