"""Hardware peaks + HLO latency estimator.

The estimator tests are *oracles*: hand-written HLO modules against a
synthetic LatencyDB where the exact expected nanoseconds are computed by hand
from the documented pricing rules — trip-count rollup, lane amortization, the
matmul fma-equivalent term, the chase-ladder memory term, and the coverage
fraction. A change to any pricing rule must show up here as a changed
constant, never as a silently different total.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.core import chains, hlo_analysis, perfmodel
from repro.core.latency_db import LatencyDB, LatencyRecord


def _rec(op, ns, cat="fp32", dtype="float32", opt="O3", notes="", env=None):
    env = env or {"device_kind": "cpu", "backend": "cpu", "jax_version": "x"}
    return LatencyRecord(op=op, category=cat, dtype=dtype, opt_level=opt,
                         latency_ns=ns, mad_ns=0, cycles=ns, guard=0,
                         net_latency_ns=ns, n_samples=5, measured_at="t",
                         notes=notes, **env)


def test_knee():
    assert perfmodel.TPU_V5E.arithmetic_intensity_knee == pytest.approx(
        197e12 / 819e9)


# =========================================================== estimator oracles
# Hand-written modules: every shape/count below is chosen so the expected ns
# is computable on paper. lanes=8, THROUGHPUT_FACTOR=0.25 throughout.

ELEMWISE_HLO = """
HloModule elemwise

ENTRY %main (a: f32[256], b: f32[256]) -> f32[256] {
  %a = f32[256] parameter(0)
  %b = f32[256] parameter(1)
  ROOT %s = f32[256] add(f32[256] %a, f32[256] %b)
}
"""

WHILE_HLO = """
HloModule rollup

%body (p0: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p0 = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8]) %p0), index=0
  %x = f32[8] get-tuple-element((s32[], f32[8]) %p0), index=1
  %t = f32[8] tanh(f32[8] %x)
  %one = s32[] constant(1)
  %ni = s32[] add(s32[] %i, s32[] %one)
  ROOT %r = (s32[], f32[8]) tuple(s32[] %ni, f32[8] %t)
}

%cond (p1: (s32[], f32[8])) -> pred[] {
  %p1 = (s32[], f32[8]) parameter(0)
  %ii = s32[] get-tuple-element((s32[], f32[8]) %p1), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(s32[] %ii, s32[] %n), direction=LT
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8]) tuple(s32[] %z, f32[8] %a)
  %w = (s32[], f32[8]) while((s32[], f32[8]) %init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8] get-tuple-element((s32[], f32[8]) %w), index=1
}
"""

DOT_HLO = """
HloModule matmul

ENTRY %main (a: f32[4,8], b: f32[8,16]) -> f32[4,16] {
  %a = f32[4,8] parameter(0)
  %b = f32[8,16] parameter(1)
  ROOT %d = f32[4,16] dot(f32[4,8] %a, f32[8,16] %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

MIXED_HLO = """
HloModule mixed

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %t = f32[8] tanh(f32[8] %a)
  ROOT %f = f32[8] floor(f32[8] %t)
}
"""


def test_dynamic_histogram_rolls_trip_counts():
    flat = hlo_analysis.op_histogram(WHILE_HLO)
    dyn = hlo_analysis.dynamic_op_histogram(WHILE_HLO)
    assert flat[("tanh", 8)] == 1
    assert dyn[("tanh", 8)] == 5.0          # while body x known_trip_count
    assert dyn[("add", 1)] == 5.0


def test_oracle_lane_amortization():
    """One top-level f32[256] add: 1 issue + 255 amortized elements.

    expected = lat + (256-1)/8 * 0.25 * lat = 2 + 255/32 * 2 = 17.9375
    """
    db = LatencyDB()
    db.add(_rec("add.float32", 2.0))
    r = perfmodel.HloLatencyEstimator(db).estimate(ELEMWISE_HLO)
    assert r.compute_ns == pytest.approx(2.0 + (255 / 8) * 0.25 * 2.0)
    assert r.compute_ns == pytest.approx(17.9375)
    assert r.coverage == 1.0
    assert r.memory_ns == 0.0               # no ladder in the DB
    assert r.total_ns == r.compute_ns


def test_oracle_trip_count_rollup():
    """While body priced x n=5: 5 tanh(f32[8]) + 5 add(s32[]).

    tanh: 5 * (10 + 7/8*0.25*10) = 5 * 12.1875 = 60.9375
    add (via add.float32 row): 5 * 2 = 10       => 70.9375 total
    """
    db = LatencyDB()
    db.add(_rec("tanh", 10.0, cat="special_math"))
    db.add(_rec("add.float32", 2.0))
    r = perfmodel.HloLatencyEstimator(db).estimate(WHILE_HLO)
    assert r.compute_ns == pytest.approx(70.9375)
    assert r.coverage == 1.0
    assert r.priced_instances == 10.0
    # the special_math and fp32 classes split exactly
    assert r.by_class["special_math"].ns == pytest.approx(60.9375)
    assert r.by_class["special_math"].instances == 5.0
    assert r.by_class["fp32"].ns == pytest.approx(10.0)


def test_oracle_matmul_fma_pricing():
    """dot[4,16]x[8 contracting]: 1024 flops = 512 fma-equivalents.

    expected = 1*4 + (512-1)/8 * 0.25 * 4 = 4 + 63.875 = 67.875
    """
    db = LatencyDB()
    db.add(_rec("fma.float32", 4.0))
    r = perfmodel.HloLatencyEstimator(db).estimate(DOT_HLO)
    assert r.compute_ns == pytest.approx(4.0 + (511 / 8) * 0.25 * 4.0)
    assert r.by_class["matmul"].instances == 1.0
    assert r.by_class["matmul"].elements == pytest.approx(512.0)
    assert r.coverage == 1.0


def test_oracle_memory_term():
    """f32[256] add at top level: 3*1024 HBM bytes.

    ladder rung ws4096 @ 6.4ns/64B line -> 0.1 ns/B; mem_streams=8
    memory_ns = 3072 * 0.1 / 8 = 38.4, which exceeds compute (17.9375).
    """
    db = LatencyDB()
    db.add(_rec("add.float32", 2.0))
    db.add(_rec("mem.chase.ws4096", 6.4, cat="memory", dtype="int32",
                notes="cold_ns=1 stride=64"))
    r = perfmodel.HloLatencyEstimator(db).estimate(ELEMWISE_HLO)
    assert r.bytes_accessed == 3072.0
    assert r.memory_ns == pytest.approx(38.4)
    assert r.compute_ns == pytest.approx(17.9375)
    assert r.total_ns == pytest.approx(38.4)
    assert r.bound == "memory"


def test_oracle_stream_memory_term():
    """A streaming-bandwidth row prices the bytes directly, ahead of the
    chase ladder: 0.64 ns per 64 B line -> 3072 * 0.01 = 30.72."""
    db = LatencyDB()
    db.add(_rec("add.float32", 2.0))
    db.add(_rec("mem.chase.ws4096", 6.4, cat="memory", dtype="int32",
                notes="cold_ns=1 stride=64"))
    db.add(_rec("mem.stream", 0.64, cat="memory", notes="line=64"))
    r = perfmodel.HloLatencyEstimator(db).estimate(ELEMWISE_HLO)
    assert r.memory_ns == pytest.approx(30.72)
    assert r.total_ns == pytest.approx(30.72)


def test_oracle_launch_term():
    """The clock_overhead row at the estimator's opt level is one call's
    round trip: it adds to the total and shows as its own class."""
    db = LatencyDB()
    db.add(_rec("add.float32", 2.0))
    db.add(_rec("clock_overhead", 500.0, cat="overhead", opt="O3"))
    db.add(_rec("clock_overhead", 9000.0, cat="overhead", opt="O0"))
    r = perfmodel.HloLatencyEstimator(db).estimate(ELEMWISE_HLO)
    assert r.launch_ns == 500.0
    assert r.total_ns == pytest.approx(500.0 + 17.9375)
    assert r.by_class["launch"].ns == 500.0
    assert r.coverage == 1.0                 # the call is not an op instance
    assert "launch=500.0" in r.summary()


TPU_ENV = {"device_kind": "TPU v5 lite", "backend": "tpu", "jax_version": "x"}


def _device_db(ik_mad=0.01):
    db = LatencyDB()
    db.add(_rec("add.float32", 100.0, env=TPU_ENV))    # dispatch: ignored
    db.add(dataclasses.replace(
        _rec("inkernel.add.float32", 6.0, env=TPU_ENV,
             notes="pallas fori_loop chain lens=(1, 2) tile=(8, 128)"),
        mad_ns=ik_mad))
    db.add(_rec("fma.float32", 4.0, env=TPU_ENV))
    return db


def test_oracle_device_rows_price_from_inkernel_tiles():
    """On an accelerator the in-kernel row prices compute and one issue
    covers its (8, 128) tile: 6 + 255/1024 * 0.25 * 6."""
    est = perfmodel.HloLatencyEstimator(_device_db(), filters=TPU_ENV)
    assert est.device_rows
    r = est.estimate(ELEMWISE_HLO)
    assert r.compute_ns == pytest.approx(6.0 + 255 / 1024 * 0.25 * 6.0)
    assert r.coverage == 1.0


def test_device_rows_skip_unresolved_rows_and_leave_dots_unpriced():
    """A row within 3 MADs of zero is noise, not a latency; a dot is
    matrix-unit work no VPU chain row prices."""
    est = perfmodel.HloLatencyEstimator(_device_db(ik_mad=2.5),
                                        filters=TPU_ENV)
    r = est.estimate(ELEMWISE_HLO)
    assert r.coverage == 0.0 and dict(r.unpriced_opcodes) == {"add": 1.0}
    r = est.estimate(DOT_HLO)
    assert r.coverage == 0.0 and dict(r.unpriced_opcodes) == {"dot": 1.0}
    assert r.compute_ns == est.default_ns


def test_memory_ladder_rung_selection_and_inkernel_preference():
    db = LatencyDB()
    db.add(_rec("mem.chase.ws4096", 4.0, cat="memory", dtype="int32",
                notes="stride=64"))
    db.add(_rec("mem.chase.ws1048576", 40.0, cat="memory", dtype="int32",
                notes="stride=64"))
    # in-kernel twin at the small rung wins over the host row
    db.add(_rec("inkernel.mem.4096", 2.0, cat="memory", dtype="int32",
                notes="ws=4096 line=64 space=vmem"))
    # fidelity-suffixed rows are different experiments: never in the ladder
    db.add(_rec("inkernel.mem.4096.vmem", 99.0, cat="memory", dtype="int32",
                notes="ws=4096 line=64 space=vmem"))
    est = perfmodel.HloLatencyEstimator(db)
    ladder = est.memory_ladder()
    assert [(g.working_set_bytes, g.ns_per_line, g.source) for g in ladder] \
        == [(4096, 2.0, "inkernel"), (1048576, 40.0, "host")]
    # footprint 3072 fits the 4 KiB rung: 3072 * (2/64) / 8
    assert est._memory_ns(3072) == pytest.approx(12.0)
    # footprint beyond the deepest rung falls back to it: ns/B = 40/64
    assert est._memory_ns(1 << 21) == pytest.approx((1 << 21) * (40 / 64) / 8)


def test_oracle_coverage_fraction():
    """tanh is measured; floor has no table mapping -> default-priced.

    coverage = 1 priced / 2 countable; floor contributes
    default_ns-priced ns and shows up in unpriced_opcodes.
    """
    db = LatencyDB()
    db.add(_rec("tanh", 10.0, cat="special_math"))
    est = perfmodel.HloLatencyEstimator(db, default_ns=5.0)
    r = est.estimate(MIXED_HLO)
    assert r.coverage == pytest.approx(0.5)
    assert r.priced_instances == 1.0 and r.unpriced_instances == 1.0
    assert dict(r.unpriced_opcodes) == {"floor": 1.0}
    per_op = 7 / 8 * 0.25                   # amortized tail factor at 8 elems
    assert r.compute_ns == pytest.approx(10 * (1 + per_op) + 5 * (1 + per_op))
    assert r.by_class["unpriced"].ns == pytest.approx(5 * (1 + per_op))


def test_mapped_but_unmeasured_counts_as_unpriced():
    """A mapped opcode with no DB row prices at default_ns and lowers
    coverage — the "silently skipping" failure mode, inverted."""
    est = perfmodel.HloLatencyEstimator(LatencyDB(), default_ns=3.0)
    r = est.estimate(ELEMWISE_HLO)
    assert r.coverage == 0.0
    assert dict(r.unpriced_opcodes) == {"add": 1.0}
    assert r.compute_ns == pytest.approx(3.0 * (1 + (255 / 8) * 0.25))


CUSTOM_CALL_HLO = """
HloModule opaque

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %t = f32[8] tanh(f32[8] %a)
  ROOT %k = f32[8] custom-call(f32[8] %t), custom_call_target="my_kernel"
}
"""


def test_custom_call_counts_as_unpriced():
    """An opaque library/Pallas kernel must lower coverage, not vanish —
    its (often dominant) cost is unknowable from the tables. Unresolved
    targets are reported by name, never lumped into one bucket."""
    db = LatencyDB()
    db.add(_rec("tanh", 10.0, cat="special_math"))
    r = perfmodel.HloLatencyEstimator(db).estimate(CUSTOM_CALL_HLO)
    assert r.coverage == pytest.approx(0.5)
    assert dict(r.unpriced_opcodes) == {"custom-call:my_kernel": 1.0}


def test_structural_ops_do_not_count():
    """parameter/tuple/gte never enter the coverage denominator."""
    db = LatencyDB()
    db.add(_rec("tanh", 10.0, cat="special_math"))
    db.add(_rec("add.float32", 2.0))
    r = perfmodel.HloLatencyEstimator(db).estimate(WHILE_HLO)
    # only tanh x5 and add x5 are countable in the whole module
    assert r.priced_instances + r.unpriced_instances == 10.0


def test_estimate_ns_attaches_report():
    db = LatencyDB()
    db.add(_rec("add.float32", 2.0))
    ns = perfmodel.HloLatencyEstimator(db).estimate_ns(ELEMWISE_HLO)
    assert isinstance(ns, float) and ns > 0
    assert ns.report.coverage == 1.0        # the satellite fix: no bare float
    assert float(ns) == ns.report.total_ns
    assert "coverage" in ns.report.summary()


def test_estimator_env_filters():
    """Rows from another device fingerprint must not price this module."""
    other = {"device_kind": "tpu", "backend": "tpu", "jax_version": "y"}
    db = LatencyDB()
    db.add(_rec("inkernel.add.float32", 100.0, env=other,
                notes="tile=(8, 128)"))
    db.add(_rec("add.float32", 2.0))
    est = perfmodel.HloLatencyEstimator(
        db, filters={"device_kind": "cpu", "backend": "cpu",
                     "jax_version": "x"})
    r = est.estimate(ELEMWISE_HLO)
    assert r.compute_ns == pytest.approx(17.9375)   # priced from the cpu row
    est_tpu = perfmodel.HloLatencyEstimator(
        db, filters={"device_kind": "tpu", "backend": "tpu",
                     "jax_version": "y"})
    assert est_tpu.estimate(ELEMWISE_HLO).compute_ns > 100.0


def test_estimator_on_real_lowered_module():
    """End to end on a real jit-lowered scan: trip counts make the scanned
    tanh 8x the single-iteration price."""
    db = LatencyDB()
    db.add(_rec("tanh", 20.0, cat="special_math"))
    db.add(_rec("fma.float32", 2.0))

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return lax.scan(body, x, None, length=8)[0]

    x = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    r = perfmodel.HloLatencyEstimator(db).estimate(txt)
    assert r.by_class["special_math"].instances == 8.0
    assert r.by_class["matmul"].instances == 8.0
    # 8 x (20 + 511/8*0.25*20) tanh alone
    assert r.by_class["special_math"].ns == pytest.approx(8 * 20 * (1 + 511 / 32))
    assert r.total_ns > 0 and 0 < r.coverage <= 1.0


# ==================================================== registry <-> table map
def test_hlo_table_mapping_resolves_to_registry_rows():
    """Every HLO_TO_TABLE value must price against a row some plan emits:
    a registry OpSpec name (directly or via the base-row fallback) or a
    memory-probe row — the estimator can never consult a phantom table."""
    names = {o.name for o in chains.default_registry()}
    for opcode, table_op in hlo_analysis.HLO_TO_TABLE.items():
        base = table_op.split(".")[0]
        resolves = (table_op in names or base in names
                    or perfmodel._MEM_ROW_RE.match(table_op))
        assert resolves, f"{opcode!r} -> {table_op!r} matches no emitted row"


def test_hlo_table_mapping_rows_are_measured_rows():
    """Sharper form: with a DB holding one row per registry op, every mapping
    value resolves to a *measured* latency (covered=True), so coverage can
    reach 1.0 on a fully characterized DB."""
    db = LatencyDB()
    for o in chains.default_registry():
        db.add(_rec(o.name, 1.0, cat=o.category, dtype=o.dtype))
    est = perfmodel.HloLatencyEstimator(db)
    for table_op in set(hlo_analysis.HLO_TO_TABLE.values()):
        lat, covered, _ = est._table_latency(table_op)
        assert covered, f"{table_op!r} fell back to default_ns"


def test_table_category_classification():
    assert perfmodel._table_category("add.float32") == "fp32"
    assert perfmodel._table_category("tanh") == "special_math"
    assert perfmodel._table_category("sub") == "int_arith"
    assert perfmodel._table_category("no.such.row") == "uncategorized"


# ============================================================= serving points
def test_servingpoint_round_trip():
    rec = _rec("serving.prefill.b2p64", 1000.0, cat="serving",
               notes="phase=prefill batch=2 prompt=64 model=serving-tiny "
                     "predicted_ns=500.000 compute_ns=400.000 "
                     "memory_ns=500.000 coverage=0.8000 bound=memory")
    pt = perfmodel.servingpoint_from_record(rec)
    assert pt.phase == "prefill" and pt.batch == 2 and pt.prompt_len == 64
    assert pt.measured_ns == 1000.0 and pt.predicted_ns == 500.0
    assert pt.ratio == pytest.approx(0.5)
    assert pt.abs_log10_error == pytest.approx(0.30103, abs=1e-4)
    assert pt.coverage == pytest.approx(0.8)
    assert pt.model == "serving-tiny"


def test_servingpoint_degenerate_error_is_inf():
    rec = _rec("serving.decode.b1p16", 0.0, cat="serving",
               notes="phase=decode batch=1 prompt=16 predicted_ns=5.0 "
                     "coverage=0")
    pt = perfmodel.servingpoint_from_record(rec)
    assert pt.abs_log10_error == float("inf")
