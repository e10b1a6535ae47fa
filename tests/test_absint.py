"""Abstract interpreter (repro.analyze.absint / ranges): value-range and
quantization-error propagation over traced jaxprs.

Three layers:

* lattice units — interval arithmetic edge cases (inf endpoints, the
  0 * inf cleanup, widening convergence) on :mod:`repro.analyze.ranges`;
* seeded-regression graph tests — plant one defect (unclamped psum into a
  narrow accumulator, unguarded exp) and assert exactly that finding,
  plus the mirror test that the guarded idiom produces none;
* soundness properties — concrete evaluation of a traced function must
  land inside the interval the interpreter propagated for it, across scan
  carries, cond joins, and the quantize/dequantize idiom (hypothesis, or
  the bundled shim when the wheel is absent).
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analyze import ranges as R
from repro.analyze.absint import abstract_eval, interpret_jaxpr
from repro.analyze.ranges import INF, AbsVal


def _trace(fn, *args):
    return jax.jit(fn).trace(*args).jaxpr


def _findings(fn, *args, rules=("overflow", "numerics"), in_vals=None,
              axis_sizes=None):
    res = interpret_jaxpr(_trace(fn, *args), in_vals=in_vals,
                          axis_sizes=axis_sizes, rules=rules)
    return res


# ---------------------------------------------------------------------------
# Lattice units
# ---------------------------------------------------------------------------


class TestLattice:
    def test_join_hull(self):
        j = R.join(AbsVal(0, 1), AbsVal(3, 5))
        assert (j.lo, j.hi) == (0, 5)

    def test_join_loses_exactness_only_when_either_inexact(self):
        assert R.join(AbsVal(0, 1, exact=True), AbsVal(2, 3, exact=True)).exact
        assert not R.join(AbsVal(0, 1, exact=True), AbsVal(2, 3)).exact

    def test_widen_jumps_to_infinity(self):
        w = R.widen(AbsVal(0, 1), AbsVal(0, 2))
        assert w.hi == INF and w.lo == 0
        w = R.widen(AbsVal(0, 1), AbsVal(-1, 1))
        assert w.lo == -INF and w.hi == 1

    def test_widen_fixpoint_is_stable(self):
        w = R.widen(AbsVal(0, INF), AbsVal(0, INF))
        assert w == AbsVal(0, INF)

    def test_mul_zero_times_inf_is_conservative(self):
        m = R.mul(AbsVal(0, 0), AbsVal(-INF, INF))
        assert m.contains(0.0)

    def test_nan_endpoints_normalized(self):
        v = AbsVal(math.nan, math.nan)
        assert (v.lo, v.hi) == (-INF, INF)

    def test_empty_interval_normalized_to_top(self):
        v = AbsVal(3, 1)
        assert (v.lo, v.hi) == (-INF, INF)

    def test_sub_of_intervals(self):
        s = R.sub(AbsVal(0, 1), AbsVal(2, 3))
        assert (s.lo, s.hi) == (-3, -1)

    def test_div_through_zero_is_unbounded(self):
        d = R.div(AbsVal(1, 1), AbsVal(-1, 1))
        assert d.hi == INF and d.lo == -INF

    def test_scale_by_count(self):
        s = R.scale_by_count(AbsVal(-3, 7, exact=True), 4)
        assert (s.lo, s.hi) == (-12, 28)
        assert s.exact

    def test_clamp_meets_bounds(self):
        c = R.clamp(AbsVal(0, 0), AbsVal(-INF, INF), AbsVal(255, 255))
        assert (c.lo, c.hi) == (0, 255)

    def test_exp_of_nonpositive_bounded_by_one(self):
        e = R.exp(AbsVal(-INF, 0))
        assert e.lo == 0 and e.hi <= 1.0 + 1e-12

    def test_qerr_scales_through_mul(self):
        q = R.mul(AbsVal(-1, 1, qerr=0.5), AbsVal(2, 2))
        assert q.qerr == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Seeded graph regressions: one defect -> one finding; idiom -> none
# ---------------------------------------------------------------------------


# Tracing needs only the mesh's shape, so an abstract mesh stands in for four
# devices this process may not have.
_CLIENTS_MESH = jax.sharding.AbstractMesh((4,), ("clients",))


class TestOverflowRule:
    def _quant_allreduce(self, wire_dtype):
        def step(g):
            codes = jnp.clip(jnp.round(g * 255.0), 0, 255)
            return jax.lax.psum(codes.astype(wire_dtype), "clients")

        mesh = _CLIENTS_MESH
        P = jax.sharding.PartitionSpec

        def run(g):
            return jax.shard_map(step, mesh=mesh, in_specs=(P(),),
                                 out_specs=P())(g)

        x = jax.ShapeDtypeStruct((16,), jnp.float32)
        return jax.jit(run).trace(x).jaxpr

    def test_clipped_codes_into_wide_accumulator_prove(self):
        jaxpr = self._quant_allreduce(jnp.int32)
        res = interpret_jaxpr(jaxpr, axis_sizes={"clients": 4},
                              rules=("overflow",))
        assert not res.findings
        ps = [p for p in res.proofs if p["kind"] == "psum"]
        assert ps and all(p["ok"] for p in ps)
        # 4 * 255 = 1020 against int32: > 20 bits of headroom
        assert ps[0]["worst_sum"] == pytest.approx(1020)
        assert ps[0]["headroom_bits"] >= 20

    def test_seeded_negative_narrow_accumulator(self):
        jaxpr = self._quant_allreduce(jnp.int8)
        res = interpret_jaxpr(jaxpr, axis_sizes={"clients": 4},
                              rules=("overflow",))
        errs = [f for f in res.findings
                if f.rule == "overflow.wire_accumulator"]
        assert len(errs) == 1
        assert errs[0].severity == "error"
        assert "int8" in errs[0].message

    def test_unclamped_int_sum_flagged(self):
        def step(x):
            return jax.lax.psum(x, "clients")

        mesh = _CLIENTS_MESH
        P = jax.sharding.PartitionSpec

        def run(x):
            return jax.shard_map(step, mesh=mesh, in_specs=(P(),),
                                 out_specs=P())(x)

        jaxpr = jax.jit(run).trace(
            jax.ShapeDtypeStruct((8,), jnp.int32)).jaxpr
        res = interpret_jaxpr(jaxpr, axis_sizes={"clients": 4},
                              rules=("overflow",))
        errs = [f for f in res.findings
                if f.rule == "overflow.wire_accumulator"]
        assert len(errs) == 1
        assert "no provable bound" in errs[0].message


class TestNumericsRule:
    def test_unguarded_exp_flagged(self):
        res = _findings(lambda x: jnp.exp(x).sum(), jnp.zeros((8,)))
        assert [f.rule for f in res.findings] == ["numerics.unguarded"]

    def test_softmax_idiom_proven(self):
        res = _findings(lambda x: jax.nn.softmax(x, axis=-1),
                        jnp.zeros((4, 8)))
        assert not res.findings

    def test_online_softmax_scan_carry_proven(self):
        """m_new = max(m, rowmax(s)) needs the two-var max branch."""

        def online(s_all):
            def body(carry, s):
                m, acc = carry
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                return (m_new, acc * corr[..., None] + p.sum(-1,
                        keepdims=True)), ()

            m0 = jnp.full((4,), -jnp.inf, jnp.float32)
            a0 = jnp.zeros((4, 1), jnp.float32)
            (m, acc), _ = jax.lax.scan(body, (m0, a0), s_all)
            return acc

        res = _findings(online, jnp.zeros((3, 4, 8)))
        assert not res.findings

    def test_guarded_log_clean_unguarded_flagged(self):
        clean = _findings(lambda x: jnp.log(jnp.maximum(x, 1e-9)),
                          jnp.ones((4,)))
        assert not clean.findings
        dirty = _findings(lambda x: jnp.log(x), jnp.ones((4,)))
        assert [f.rule for f in dirty.findings] == ["numerics.unguarded"]

    def test_div_by_eps_guarded_clean(self):
        clean = _findings(lambda x: x / (jnp.abs(x) + 1e-6), jnp.ones((4,)))
        assert not clean.findings


# ---------------------------------------------------------------------------
# Soundness properties: concrete eval lands inside the propagated interval
# ---------------------------------------------------------------------------


def _out_intervals(fn, *tmpl, in_vals=None):
    return abstract_eval(jax.jit(fn).trace(*tmpl).jaxpr, in_vals)


def _assert_inside(val, iv: AbsVal, slack=1e-6):
    arr = np.asarray(val, dtype=np.float64)
    assert np.all(arr >= iv.lo - slack), (arr.min(), iv)
    assert np.all(arr <= iv.hi + slack), (arr.max(), iv)


class TestSoundness:
    @settings(max_examples=20, deadline=None)
    @given(x=st.floats(-50.0, 50.0), bits=st.sampled_from([2, 4, 8]))
    def test_dequant_idiom(self, x, bits):
        """round(x/step)*step stays in the interval AND within qerr."""
        step = 2.0 / (2 ** bits - 1)

        def deq(v):
            codes = jnp.round(v / step)
            return codes * step

        tmpl = jax.ShapeDtypeStruct((4,), jnp.float32)
        (iv,) = _out_intervals(deq, tmpl,
                               in_vals=[AbsVal(-abs(x), abs(x))])
        v = np.clip(np.array([x, -x, x / 3, 0.0], np.float32),
                    -abs(x), abs(x))
        out = jax.jit(deq)(v)
        _assert_inside(out, iv, slack=step)
        assert iv.qerr >= step * 0.5 - 1e-12

    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(1, 6), x0=st.floats(-2.0, 2.0))
    def test_scan_carry(self, n, x0):
        """Decaying scan carry stays inside the widened fixpoint."""

        def run(x):
            def body(c, _):
                return 0.5 * c + jnp.clip(x.sum(), -1.0, 1.0), ()

            c, _ = jax.lax.scan(body, 0.0, jnp.arange(n))
            return c

        tmpl = jax.ShapeDtypeStruct((2,), jnp.float32)
        (iv,) = _out_intervals(run, tmpl,
                               in_vals=[AbsVal(-abs(x0), abs(x0))])
        out = jax.jit(run)(jnp.array([x0 / 2, x0 / 2], jnp.float32))
        _assert_inside(out, iv)

    @settings(max_examples=15, deadline=None)
    @given(x=st.floats(-10.0, 10.0), flag=st.booleans())
    def test_cond_join(self, x, flag):
        """cond output lands inside the join of both branch intervals."""

        def run(p, v):
            return jax.lax.cond(p, lambda v: jnp.tanh(v),
                                lambda v: jnp.clip(v, -2.0, 2.0), v)

        tmpl_p = jax.ShapeDtypeStruct((), jnp.bool_)
        tmpl_v = jax.ShapeDtypeStruct((), jnp.float32)
        (iv,) = _out_intervals(run, tmpl_p, tmpl_v,
                               in_vals=[None, AbsVal(-abs(x), abs(x))])
        out = jax.jit(run)(jnp.asarray(flag), jnp.float32(x))
        _assert_inside(out, iv)

    @settings(max_examples=10, deadline=None)
    @given(x=st.floats(0.1, 100.0))
    def test_rsqrt_monotone(self, x):
        def run(v):
            return jax.lax.rsqrt(v + 1e-6)

        tmpl = jax.ShapeDtypeStruct((), jnp.float32)
        (iv,) = _out_intervals(run, tmpl, in_vals=[AbsVal(0.1, 100.0)])
        out = jax.jit(run)(jnp.float32(x))
        _assert_inside(out, iv)


# ---------------------------------------------------------------------------
# Fixpoint behavior
# ---------------------------------------------------------------------------


class TestFixpoints:
    def test_growing_carry_widens_not_diverges(self):
        def run(x):
            def body(c, _):
                return c + x.sum(), ()

            c, _ = jax.lax.scan(body, 0.0, jnp.arange(1000))
            return c

        tmpl = jax.ShapeDtypeStruct((2,), jnp.float32)
        (iv,) = _out_intervals(run, tmpl, in_vals=[AbsVal(0.0, 1.0)])
        # must terminate (widening) and stay sound: sum of positives
        assert iv.lo >= 0.0 and iv.hi == INF

    def test_while_loop_counter_bounded_below(self):
        def run(x):
            def cond(c):
                return c[0] < 10.0

            def body(c):
                return (c[0] + 1.0, jnp.minimum(c[1], 0.0))

            return jax.lax.while_loop(cond, body, (x, x))[1]

        tmpl = jax.ShapeDtypeStruct((), jnp.float32)
        (iv,) = _out_intervals(run, tmpl, in_vals=[AbsVal(0.0, 1.0)])
        assert iv.hi <= 0.0 + 1e-12 or iv.hi <= 1.0  # min() keeps hi <= 1
