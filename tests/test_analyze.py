"""Static analysis (repro.analyze): precision flow, wire lint, kernel checker.

Seeded-regression contract: each rule family has a test that plants exactly
one defect and asserts exactly ONE finding with file/op provenance — and a
matching test that the shipped code produces none.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analyze.allowlist import AllowEntry, apply_allowlist, load_allowlist
from repro.analyze.findings import Finding, at_or_above, worst_severity
from repro.analyze.kernel_check import check_kernel_spec, shipped_kernel_specs
from repro.analyze.precision_flow import lint_jaxpr
from repro.analyze.wire_lint import (WireContext, check_comm_report,
                                     expected_gathers, lint_module)
from repro.api.precision import PrecisionPolicy
from repro.kernels.spec import BlockOperand, KernelSpec, ScratchSpec
from repro.roofline.hlo_parse import CollectiveOp, ModuleCosts, parse_module

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "hlo")


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# hlo_parse hardening: CollectiveOp records from checked-in HLO text
# ---------------------------------------------------------------------------


class TestHloCollectiveRecords:
    def test_f32_allreduce_record(self):
        mc = parse_module(_fixture("allreduce_f32.txt"))
        recs = [r for r in mc.collectives if r.kind == "all-reduce"]
        assert len(recs) == 1
        r = recs[0]
        assert r.dtype == "f32"
        assert r.elems == 1024 * 256
        assert r.group_size == 4
        assert r.name == "%all-reduce.1"
        assert r.wire_bytes == pytest.approx(2 * 3 / 4 * 1024 * 256 * 4)

    def test_start_done_pair_counted_once(self):
        mc = parse_module(_fixture("allreduce_start_done.txt"))
        recs = [r for r in mc.collectives if r.kind == "all-reduce"]
        assert len(recs) == 1, "the -done half must not double-count"
        assert recs[0].elems == 512 * 128
        assert mc.collective_counts.get("all-reduce") == 1

    def test_tuple_parts_summed(self):
        mc = parse_module(_fixture("allreduce_tuple.txt"))
        recs = [r for r in mc.collectives if r.kind == "all-reduce"]
        assert len(recs) == 1
        assert recs[0].parts == (("s32", 100), ("s32", 156))
        assert recs[0].elems == 256

    def test_degenerate_group_moves_nothing(self):
        mc = parse_module(_fixture("degenerate_group.txt"))
        recs = [r for r in mc.collectives if r.kind == "all-reduce"]
        assert len(recs) == 1
        assert recs[0].group_size == 1
        assert recs[0].wire_bytes == 0.0
        assert mc.collective_bytes == 0.0


# ---------------------------------------------------------------------------
# wire lint
# ---------------------------------------------------------------------------


def _ctx(**kw):
    kw.setdefault("policy", PrecisionPolicy(comm=8))
    kw.setdefault("kind", "train")
    kw.setdefault("n_clients", 4)
    return WireContext(**kw)


def _mc(*records):
    return ModuleCosts(flops=0, dot_bytes=0, collective_bytes=0,
                       collective_by_kind={}, collective_counts={},
                       n_while=0, collectives=list(records))


def _rec(kind, dtype, elems, group=4, **kw):
    kw.setdefault("bytes", 0.0)
    kw.setdefault("wire_bytes", 0.0)
    kw.setdefault("mult", 1.0)
    kw.setdefault("name", f"%{kind}.0")
    kw.setdefault("computation", "%main.0")
    return CollectiveOp(kind=kind, dtype=dtype, elems=elems,
                        group_size=group, **kw)


class TestWireLint:
    def test_f32_allreduce_under_low_bit_comm_exactly_one(self):
        mc = parse_module(_fixture("allreduce_f32.txt"))
        found = lint_module(mc, _ctx(), cell="t")
        assert len(found) == 1
        f = found[0]
        assert f.rule == "wire.f32_allreduce"
        assert f.severity == "error"
        assert "%all-reduce.1" in f.where

    def test_uncompressed_context_not_flagged(self):
        mc = parse_module(_fixture("allreduce_f32.txt"))
        assert lint_module(mc, _ctx(kind="decode")) == []
        assert lint_module(mc, _ctx(n_clients=1)) == []
        assert lint_module(
            mc, _ctx(policy=PrecisionPolicy())) == []   # comm=32

    def test_degenerate_group_never_flagged(self):
        mc = parse_module(_fixture("degenerate_group.txt"))
        assert lint_module(mc, _ctx()) == []

    def test_narrow_allreduce(self):
        # wire_dtype(comm=8, n=4) = int16; s8 accumulator overflows
        found = lint_module(_mc(_rec("all-reduce", "s8", 4096)), _ctx())
        assert [f.rule for f in found] == ["wire.narrow_allreduce"]
        assert found[0].severity == "error"

    def test_wide_allreduce_warns(self):
        found = lint_module(_mc(_rec("all-reduce", "s32", 4096)), _ctx())
        assert [f.rule for f in found] == ["wire.wide_allreduce"]
        assert found[0].severity == "warn"

    def test_matching_width_clean(self):
        found = lint_module(_mc(_rec("all-reduce", "s16", 4096)), _ctx())
        assert found == []

    def test_unexpected_allgather(self):
        ctx = _ctx(kind="decode", fsdp=2,
                   expected_gather_dtypes=expected_gathers(
                       fsdp=2, tp=1, packed=True))
        ok = lint_module(_mc(_rec("all-gather", "s8", 4096, group=2)), ctx)
        assert ok == []
        bad = lint_module(_mc(_rec("all-gather", "f16", 4096, group=2)), ctx)
        assert [f.rule for f in bad] == ["wire.unexpected_allgather"]

    def test_pure_dp_mesh_expects_no_gathers(self):
        assert expected_gathers(fsdp=1, tp=1, packed=False) == frozenset()
        ctx = _ctx(expected_gather_dtypes=frozenset())
        bad = lint_module(_mc(_rec("all-gather", "f32", 4096)), ctx)
        assert [f.rule for f in bad] == ["wire.unexpected_allgather"]


class TestCommReportConsistency:
    def test_matching_report_clean(self):
        mc = parse_module(_fixture("allreduce_tuple.txt"))
        report = {"wire_dtype": "int32", "replicated_elems": 256}
        assert check_comm_report(mc, report) == []

    def test_doctored_report_flagged_once(self):
        mc = parse_module(_fixture("allreduce_tuple.txt"))
        report = {"wire_dtype": "int32", "replicated_elems": 300}
        found = check_comm_report(mc, report, cell="t")
        assert len(found) == 1
        assert found[0].rule == "wire.comm_report_mismatch"
        assert found[0].severity == "error"

    def test_uncompressed_report_noop(self):
        mc = parse_module(_fixture("allreduce_f32.txt"))
        assert check_comm_report(mc, {"wire_dtype": "none"}) == []
        assert check_comm_report(mc, {"wire_dtype": "float32"}) == []


# ---------------------------------------------------------------------------
# precision-flow lint (taint walk over traced jaxprs)
# ---------------------------------------------------------------------------


LAZY = PrecisionPolicy.lazy_int8()


class TestPrecisionFlow:
    def test_eager_dequant_matmul_exactly_one(self):
        def step(x, codes, scale):
            w = codes.astype(jnp.float32) * scale     # eager dequant
            return x @ w

        traced = jax.jit(step).trace(
            _sds((4, 64), jnp.float32), _sds((64, 64), jnp.int8),
            _sds((), jnp.float32))
        found = [f for f in lint_jaxpr(traced.jaxpr, policy=LAZY)
                 if f.severity == "error"]
        assert len(found) == 1
        f = found[0]
        assert f.rule == "precision.eager_dequant"
        assert "test_analyze.py" in f.key            # file provenance
        assert "rhs" in f.message

    def test_scan_body_dequant_reported_once(self):
        def step(x, codes, scale):
            def body(h, c):
                return h @ (c.astype(jnp.float32) * scale), ()
            h, _ = jax.lax.scan(body, x, codes)
            return h

        traced = jax.jit(step).trace(
            _sds((4, 64), jnp.float32), _sds((3, 64, 64), jnp.int8),
            _sds((), jnp.float32))
        found = [f for f in lint_jaxpr(traced.jaxpr, policy=LAZY)
                 if f.rule == "precision.eager_dequant"]
        assert len(found) == 1, "loop fixpoint must dedupe per-layer reports"

    def test_quant_matmul_fast_path_clean(self):
        from repro.kernels.ops import quant_matmul

        traced = jax.jit(quant_matmul).trace(
            _sds((8, 128), jnp.float32), _sds((128, 128), jnp.int8),
            _sds((), jnp.float32))
        found = lint_jaxpr(traced.jaxpr, policy=LAZY, expect_fastpath=True)
        assert found == []

    def test_no_fastpath_warning(self):
        traced = jax.jit(lambda x, w: x @ w).trace(
            _sds((4, 64), jnp.float32), _sds((64, 64), jnp.float32))
        found = lint_jaxpr(traced.jaxpr, policy=LAZY, expect_fastpath=True)
        assert [f.rule for f in found] == ["precision.no_fastpath"]
        assert found[0].severity == "warn"
        # not expected (e.g. prefill): no warning
        assert lint_jaxpr(traced.jaxpr, policy=LAZY,
                          expect_fastpath=False) == []

    def test_int32_token_ids_do_not_taint(self):
        def step(tokens, table, w):
            x = jnp.take(table, tokens, axis=0)       # embedding gather
            return x @ w

        traced = jax.jit(step).trace(
            _sds((4,), jnp.int32), _sds((100, 64), jnp.float32),
            _sds((64, 64), jnp.float32))
        found = [f for f in lint_jaxpr(traced.jaxpr, policy=LAZY)
                 if f.rule == "precision.eager_dequant"]
        assert found == []

    def test_narrow_psum_accumulator_exactly_one(self):
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
        fn = jax.shard_map(lambda c: jax.lax.psum(c, "x"), mesh=mesh,
                           in_specs=P(), out_specs=P())
        traced = jax.jit(fn).trace(_sds((4, 64), jnp.int8))
        # lint as if the axis had 4 participants: 4*(2^8-1) needs int16
        found = lint_jaxpr(traced.jaxpr,
                           policy=PrecisionPolicy(comm=8),
                           axis_sizes={"x": 4})
        assert [f.rule for f in found] == ["precision.narrow_accumulator"]
        assert found[0].severity == "error"
        assert "test_analyze.py" in found[0].key
        # a wide-enough accumulator is clean
        fn32 = jax.shard_map(lambda c: jax.lax.psum(c, "x"), mesh=mesh,
                             in_specs=P(), out_specs=P())
        traced32 = jax.jit(fn32).trace(_sds((4, 64), jnp.int32))
        assert lint_jaxpr(traced32.jaxpr, policy=PrecisionPolicy(comm=8),
                          axis_sizes={"x": 4}) == []


# ---------------------------------------------------------------------------
# kernel checker
# ---------------------------------------------------------------------------


class TestKernelChecker:
    def test_shipped_kernels_clean(self):
        for spec in shipped_kernel_specs():
            assert check_kernel_spec(spec) == [], spec.name

    def test_index_map_skipping_last_k_step(self):
        from repro.kernels.quant_matmul import kernel_spec

        spec = kernel_spec(8, 1024, 256)              # grid k-extent 2
        assert spec.grid[2] == 2
        x = spec.inputs[0]
        broken = dataclasses.replace(
            spec, inputs=(dataclasses.replace(
                x, index_map=lambda i, j, k: (i, 0)),) + spec.inputs[1:])
        found = check_kernel_spec(broken, cell="seeded")
        assert len(found) == 1
        f = found[0]
        assert f.rule == "kernel.coverage_gap"
        assert f.key == "quant_matmul:x"
        assert "quant_matmul.py" in f.where

    def test_block_overrunning_unaligned_k(self):
        from repro.kernels.quant_matmul import (_out_map, _scale_map,
                                                _w_map, _x_map)

        # K=130 NOT padded to the 128 block: the second k step overruns
        spec = KernelSpec(
            name="quant_matmul", source="quant_matmul.py:seeded",
            grid=(1, 1, 2),
            inputs=(BlockOperand("x", (8, 130), (8, 128), _x_map),
                    BlockOperand("codes", (256, 128), (128, 128), _w_map),
                    BlockOperand("scale", (1, 1), (1, 1), _scale_map,
                                 coverage="any")),
            outputs=(BlockOperand("out", (8, 128), (8, 128), _out_map),))
        found = check_kernel_spec(spec, cell="seeded")
        assert len(found) == 1
        assert found[0].rule == "kernel.oob_dma"
        assert found[0].key == "quant_matmul:x"

    def test_scratch_dtype_rule(self):
        from repro.kernels.quant_matmul import kernel_spec

        spec = kernel_spec(8, 512, 256)
        broken = dataclasses.replace(
            spec, scratch=(ScratchSpec("acc", spec.scratch[0].shape,
                                       "bfloat16", binds="out"),))
        found = check_kernel_spec(broken)
        assert [f.rule for f in found] == ["kernel.scratch_dtype"]

    def test_scratch_shape_rule(self):
        from repro.kernels.quant_matmul import kernel_spec

        spec = kernel_spec(8, 512, 256)
        broken = dataclasses.replace(
            spec, scratch=(ScratchSpec("acc", (8, 8), "float32",
                                       binds="out"),))
        found = check_kernel_spec(broken)
        assert [f.rule for f in found] == ["kernel.scratch_shape"]

    def test_wrapper_padding_matches_choose_blocks(self):
        from repro.kernels.quant_matmul import choose_blocks, kernel_spec

        # ragged decode shapes: the spec must mirror ops.quant_matmul's pad
        for m, k, n in [(1, 64, 64), (3, 513, 2048), (7, 130, 384)]:
            spec = kernel_spec(m, k, n)
            bm, bn, bk = choose_blocks(m, k, n)
            assert spec.inputs[0].shape[0] % bm == 0
            assert spec.inputs[0].shape[1] % bk == 0
            assert check_kernel_spec(spec) == [], (m, k, n)


# ---------------------------------------------------------------------------
# allowlist + severity plumbing
# ---------------------------------------------------------------------------


class TestAllowlist:
    def _finding(self, **kw):
        kw.setdefault("rule", "precision.eager_dequant")
        kw.setdefault("severity", "error")
        kw.setdefault("message", "m")
        kw.setdefault("key", "ops.py:expert_dispatch")
        return Finding(**kw)

    def test_apply_and_gate(self):
        entries = [AllowEntry(rule="precision.*", key="ops.py:*",
                              reason="per-channel scale ABI")]
        f = self._finding()
        out = apply_allowlist([f], entries)
        assert out[0].allowed and out[0].allow_reason
        assert at_or_above(out, "error") == []
        # non-matching key stays gating
        other = apply_allowlist([self._finding(key="layers.py:mlp")], entries)
        assert not other[0].allowed
        assert len(at_or_above(other, "error")) == 1

    def test_worst_severity_skips_allowed(self):
        allowed = dataclasses.replace(self._finding(), allowed=True)
        assert worst_severity([allowed]) is None
        assert worst_severity([allowed], include_allowed=True) == "error"

    def test_load_rejects_reasonless_entries(self, tmp_path):
        p = tmp_path / "analyze.toml"
        p.write_text('[[allow]]\nrule = "wire.*"\nkey = "*"\n')
        with pytest.raises(ValueError, match="reason"):
            load_allowlist(str(p))

    def test_load_roundtrip(self, tmp_path):
        p = tmp_path / "analyze.toml"
        p.write_text('[[allow]]\nrule = "wire.*"\nkey = "train:*"\n'
                     'reason = "because"\n')
        entries = load_allowlist(str(p))
        assert entries == [AllowEntry("wire.*", "train:*", "because")]
        assert load_allowlist(str(tmp_path / "missing.toml")) == []

    def test_repo_allowlist_parses(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        entries = load_allowlist(os.path.join(repo, "analyze.toml"))
        assert entries, "the checked-in analyze.toml must have entries"
        assert all(e.reason for e in entries)


# ---------------------------------------------------------------------------
# Session.analyze end-to-end (trace-only: no XLA compile)
# ---------------------------------------------------------------------------


class TestSessionAnalyze:
    @pytest.fixture(scope="class")
    def serve_findings(self):
        from repro.api.session import Session
        from repro.api.spec import RunSpec

        spec = RunSpec.from_dict({
            "arch": "yi-6b", "workload": "serve", "mesh": "1x1",
            "smoke": True, "batch": 2, "seq": 32,
            "precision": {"weights": 7, "lazy": True}})
        return Session(spec).analyze(compile=False)

    def test_serve_path_has_no_unallowlisted_errors(self, serve_findings):
        errors = at_or_above(serve_findings, "error")
        assert errors == [], [f.format() for f in errors]

    def test_packed_decode_keeps_fast_path(self, serve_findings):
        # the seeded regression this suite guards: building the decode step
        # without the session policy silently dequantizes every weight
        assert all(f.rule != "precision.no_fastpath"
                   for f in serve_findings)


# ---------------------------------------------------------------------------
# reduce-scatter accumulator contract + unknown collectives (wire lint v2)
# ---------------------------------------------------------------------------


class TestReduceScatterLint:
    def test_narrow_integer_reduce_scatter(self):
        # wire_dtype(comm=8, n=4) = int16; s8 scattered sums overflow
        found = lint_module(_mc(_rec("reduce-scatter", "s8", 4096)), _ctx())
        assert [f.rule for f in found] == ["wire.narrow_reduce_scatter"]
        assert found[0].severity == "error"

    def test_wide_integer_reduce_scatter_warns(self):
        found = lint_module(_mc(_rec("reduce-scatter", "s32", 4096)), _ctx())
        assert [f.rule for f in found] == ["wire.wide_reduce_scatter"]
        assert found[0].severity == "warn"

    def test_matching_width_clean(self):
        assert lint_module(
            _mc(_rec("reduce-scatter", "s16", 4096)), _ctx()) == []

    def test_float_reduce_scatter_is_the_fsdp_path(self):
        # FSDP gradients reduce-scatter in f32 by design: never flagged
        assert lint_module(
            _mc(_rec("reduce-scatter", "f32", 4096)), _ctx()) == []


class TestUnknownCollective:
    def test_parser_emits_conservative_record(self):
        mc = parse_module(_fixture("unknown_collective.txt"))
        recs = [r for r in mc.collectives if r.kind.startswith("unknown:")]
        assert len(recs) == 1
        r = recs[0]
        assert r.kind == "unknown:collective-broadcast"
        assert r.dtype == "f32" and r.elems == 64 * 32
        assert r.group_size == 4
        # wire bytes = full result bytes: an over- but never under-count
        assert r.wire_bytes == 64 * 32 * 4

    def test_lint_flags_unknown_kind(self):
        mc = parse_module(_fixture("unknown_collective.txt"))
        found = [f for f in lint_module(mc, _ctx())
                 if f.rule == "wire.unknown_collective"]
        assert len(found) == 1
        assert found[0].severity == "warn"
        assert "collective-broadcast" in found[0].message

    def test_known_fixture_has_no_unknown_records(self):
        mc = parse_module(_fixture("allreduce_f32.txt"))
        assert not any(r.kind.startswith("unknown:")
                       for r in mc.collectives)


# ---------------------------------------------------------------------------
# analytic overflow / error-budget proofs (static_proofs)
# ---------------------------------------------------------------------------


class TestStaticProofs:
    def test_every_comm_cell_in_both_presets_proves(self):
        from repro.analyze.static_proofs import prove_spec
        from repro.sweep.grid import get_preset

        for name in ("grad-comm-wire", "fl-codesign-grid"):
            for cell in get_preset(name).cells():
                records, findings = prove_spec(cell.spec,
                                               rules=("overflow",))
                assert findings == [], (name, cell.label,
                                        [f.format() for f in findings])
                assert all(r["ok"] for r in records), (name, cell.label)

    def test_seeded_negative_one_tier_too_narrow(self):
        from repro.analyze.static_proofs import prove_wire_accumulator

        # comm=8, n=4 needs int16; forcing int8 must fail the proof
        proof, findings = prove_wire_accumulator(8, 4, force_dtype="int8")
        assert not proof["ok"]
        assert [f.rule for f in findings] == ["overflow.wire_accumulator"]
        assert findings[0].severity == "error"
        assert "int8" in findings[0].message

    def test_headroom_matches_code_bound(self):
        from repro.analyze.static_proofs import prove_wire_accumulator
        from repro.dist.collectives import code_bound

        proof, findings = prove_wire_accumulator(8, 4)
        assert findings == [] and proof["ok"]
        assert proof["worst_sum"] == 4 * code_bound(8) == 1020
        assert proof["dtype"] == "int16"
        # int16 capacity 32767 over 1020: 5 doublings fit
        assert proof["headroom_bits"] == 5

    def test_uncompressed_comm_is_trivially_exact(self):
        from repro.analyze.static_proofs import prove_wire_accumulator

        proof, findings = prove_wire_accumulator(32, 8)
        assert findings == [] and proof["ok"]
        assert proof["kind"] == "uncompressed"

    def test_error_budget_accepts_default_policy(self):
        from repro.analyze.static_proofs import check_error_budget
        from repro.api.precision import PrecisionPolicy

        rec, findings = check_error_budget(PrecisionPolicy(), 8)
        assert findings == [], [f.format() for f in findings]
        assert rec["ok"]

    def test_error_budget_rejects_impossible_tolerance(self):
        from repro.analyze.static_proofs import check_error_budget
        from repro.api.precision import PrecisionPolicy

        # a quantized policy (full precision has zero error by definition)
        rec, findings = check_error_budget(PrecisionPolicy(weights=8), 8,
                                           lam=1e-30)
        assert not rec["ok"]
        assert findings and all(f.rule == "precision.error_budget"
                                for f in findings)
        assert all(f.severity == "error" for f in findings)

    def test_overflow_margin_table_renders(self):
        from repro.analyze.static_proofs import overflow_margin_table

        table = overflow_margin_table()
        lines = table.splitlines()
        assert lines[0].startswith("| sweep |")
        assert len(lines) > 2
        assert "**NO**" not in table      # every shipped cell proves
        assert "grad-comm-wire" in table and "fl-codesign-grid" in table


# ---------------------------------------------------------------------------
# scalar-prefetch range checks (kernel.scalar_oob)
# ---------------------------------------------------------------------------


class TestScalarOperandCheck:
    def _spec_with_scalar(self, values, lo, hi):
        from repro.kernels.spec import ScalarOperand

        op = BlockOperand("x", (8,), (8,), lambda i: (0,))
        return KernelSpec(
            name="k", source="test.py:k", grid=(1,),
            inputs=(op,), outputs=(op,),
            scalars=(ScalarOperand("page_table", np.asarray(values),
                                   lo, hi, note="pool rows"),))

    def test_in_range_values_clean(self):
        spec = self._spec_with_scalar([0, 1, 2, -1], -1, 3)
        assert [f for f in check_kernel_spec(spec)
                if f.rule == "kernel.scalar_oob"] == []

    def test_out_of_range_value_flagged(self):
        spec = self._spec_with_scalar([0, 1, 7, -1], -1, 3)
        found = [f for f in check_kernel_spec(spec)
                 if f.rule == "kernel.scalar_oob"]
        assert len(found) == 1
        assert found[0].severity == "error"
        assert "page_table" in found[0].message

    def test_shipped_decode_spec_scalars_in_range(self):
        specs = [s for s in shipped_kernel_specs() if s.scalars]
        assert specs, "the paged decode spec must export scalar operands"
        for spec in specs:
            oob = [f for f in check_kernel_spec(spec)
                   if f.rule == "kernel.scalar_oob"]
            assert oob == [], [f.format() for f in oob]


# ---------------------------------------------------------------------------
# dead-allowlist detection + differential baseline gate
# ---------------------------------------------------------------------------


class TestDeadAllowlist:
    def _f(self, rule="numerics.unguarded", key="ssm.py:ssm_block"):
        return Finding(rule=rule, severity="warn", message="m", key=key)

    def test_live_entry_not_flagged(self):
        from repro.analyze.allowlist import dead_allowlist_findings

        entries = [AllowEntry("numerics.*", "ssm.py:*", "why")]
        assert dead_allowlist_findings([self._f()], entries) == []

    def test_dead_entry_flagged_once(self):
        from repro.analyze.allowlist import (dead_allowlist_findings,
                                             dead_entries)

        entries = [AllowEntry("numerics.*", "ssm.py:*", "why"),
                   AllowEntry("precision.*", "gone.py:*", "stale")]
        findings = [self._f()]
        assert dead_entries(findings, entries) == [entries[1]]
        out = dead_allowlist_findings(findings, entries, path="analyze.toml")
        assert [f.rule for f in out] == ["meta.dead_allowlist"]
        assert out[0].severity == "warn"
        assert "gone.py:*" in out[0].message
        assert out[0].where == "analyze.toml"

    def test_no_entries_no_findings(self):
        from repro.analyze.allowlist import dead_allowlist_findings

        assert dead_allowlist_findings([self._f()], []) == []


class TestBaselineGate:
    def _f(self, rule="wire.f32_allreduce", key="train:step",
           cell="dryrun:train_4k", where="a.py:10"):
        return Finding(rule=rule, severity="error", message="m",
                       key=key, cell=cell, where=where)

    def test_identity_is_line_number_free(self):
        from repro.analyze.baseline import finding_identity

        a = self._f(where="a.py:10")
        b = self._f(where="a.py:999")
        assert finding_identity(a) == finding_identity(b)

    def test_roundtrip_and_diff(self, tmp_path):
        from repro.analyze.baseline import (diff_against_baseline,
                                            load_baseline, write_baseline)

        p = str(tmp_path / "base.json")
        write_baseline([self._f()], p)
        base = load_baseline(p)
        # known finding filtered even if its line number moved
        assert diff_against_baseline([self._f(where="a.py:999")], base) == []
        new = self._f(key="train:other")
        assert diff_against_baseline([new], base) == [new]

    def test_write_merges_extra_identities(self, tmp_path):
        from repro.analyze.baseline import load_baseline, write_baseline

        p = str(tmp_path / "base.json")
        write_baseline([self._f()], p)
        first = load_baseline(p)
        write_baseline([self._f(key="train:other")], p,
                       extra_identities=first)
        merged = load_baseline(p)
        assert first < merged and len(merged) == 2

    def test_committed_baseline_parses(self):
        from repro.analyze.baseline import load_baseline

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(repo, "results", "analyze_baseline.json")
        idents = load_baseline(path)
        assert idents, "the committed baseline must not be empty"
        assert all(len(i) == 3 for i in idents)


class TestRuleSelection:
    def test_normalize_accepts_iterables_and_strings(self):
        from repro.analyze.runner import ALL_RULE_FAMILIES, normalize_rules

        assert normalize_rules(None) is None     # None = every family
        assert set(ALL_RULE_FAMILIES) == {"precision", "wire", "kernel",
                                          "overflow", "numerics"}
        assert normalize_rules("overflow,numerics") == frozenset(
            {"overflow", "numerics"})
        assert normalize_rules(("wire",)) == frozenset({"wire"})

    def test_normalize_rejects_unknown_family(self):
        from repro.analyze.runner import normalize_rules

        with pytest.raises(ValueError, match="unknown rule"):
            normalize_rules("overflow,typo")
