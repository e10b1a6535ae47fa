"""Forward abstract interpreter over traced jaxprs (analyze v2 tentpole).

Walks the same jaxprs :mod:`repro.analyze.precision_flow` taint-walks, but
instead of boolean taint it propagates an :class:`repro.analyze.ranges.AbsVal`
per array — a value interval, an integer-exactness flag, and a
quantization-error bound — through arithmetic, the dequant idiom
(``convert_element_type`` + ``mul``-by-scale), scan/while/cond/shard_map
sub-jaxprs (loop carries widen to a fixpoint), and collectives
(``psum`` multiplies the interval by the axis size; ``all_gather`` and
``pmax`` preserve it).

Two refinements make real transformer graphs provable instead of drowning
in ⊤:

* **comparison-guarded selects** — ``where(x > k, x, fallback)`` refines the
  taken branch with the predicate, so the ``s = where(s > 0, s, 1.0)`` guard
  in the wire quantizer yields a provably positive scale;
* **the max-subtraction idiom** — ``exp(x - max(x))`` is recognized via a
  producer walk, bounding the exponent by 0 and the sum of the result below
  by 1, which keeps softmax / logsumexp free of spurious domain findings.

Rule families emitted here:

* ``overflow.wire_accumulator`` (error) — an integer ``psum`` whose interval,
  multiplied by the axis size, cannot be proven to fit its accumulator
  dtype.  The clip in ``quantized_psum_batch`` bounds the codes to
  ``±(2^bits - 1)``, so a well-formed wire path *proves* and is recorded in
  ``AbsintResult.proofs`` with its headroom; a graph missing the clamp (or
  forced one dtype tier too narrow) fails the proof statically instead of
  wrapping at runtime.
* ``numerics.unguarded`` (warn) — exp/log/div/rsqrt/sqrt consuming an
  interval containing 0 (domain edge) or of unbounded magnitude, with no
  clamp/where/eps guard visible upstream.  The static complement of the
  runtime ``on_nonfinite`` guard.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.analyze import ranges as R
from repro.analyze.findings import Finding, source_key
from repro.analyze.precision_flow import _inner, _is_var, _jaxpr_params
from repro.analyze.ranges import INF, AbsVal

#: primitives whose output carries the first operand's values unchanged (and
#: through which the max-sub / attains-one provenance walks)
_PASSTHROUGH = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev", "copy",
    "copy_p", "stop_gradient", "optimization_barrier", "reduce_precision",
    "real", "expand_dims", "sharding_constraint", "device_put",
    "pbroadcast", "pvary",
})

#: pass-through, but element-dropping: values stay bounded by the operand's
#: interval, yet "contains an element == 1" style facts do NOT survive
_SUBSET = frozenset({
    "slice", "dynamic_slice", "gather", "take", "dynamic_gather",
})

_PSUM = frozenset({"psum", "psum2", "psum_invariant"})
_RSCATTER = frozenset({"psum_scatter", "reduce_scatter"})

_BOUNDED_UNARY = {
    "tanh": (-1.0, 1.0), "sin": (-1.0, 1.0), "cos": (-1.0, 1.0),
    "logistic": (0.0, 1.0), "erf": (-1.0, 1.0), "erfc": (0.0, 2.0),
    "atan": (-math.pi / 2, math.pi / 2), "asin": (-math.pi / 2, math.pi / 2),
    "acos": (0.0, math.pi),
}

_MAX_FIX_ITERS = 5


@dataclasses.dataclass
class AbsintResult:
    """What one interpretation produced."""
    findings: list
    proofs: list          # dicts: integer-psum overflow proof certificates
    out: list             # AbsVal per jaxpr outvar


class _Scope:
    """Per-interpretation state: env + provenance used by refinements.

    ``alias`` maps an inlined sub-jaxpr's invar to the outer var it was
    bound to, so producer chases (select_n predicate refinement, the
    max-sub idiom) cross jit/remat/custom_* boundaries instead of dying
    at the first wrapper ``jnp.where`` emits.
    """

    __slots__ = ("env", "producer", "alias", "maxsub", "attains_one")

    def __init__(self):
        self.env: dict = {}
        self.producer: dict = {}
        self.alias: dict = {}
        self.maxsub: set = set()       # vars of the form x - max(x)
        self.attains_one: set = set()  # arrays containing an element == 1


def _literal_val(val) -> AbsVal:
    try:
        a = np.asarray(val)
        if a.size == 0:
            return R.TOP
        lo, hi = float(np.min(a)), float(np.max(a))
        exact = (a.dtype.kind in "iub"
                 or bool(np.all(a == np.round(a))))
        return AbsVal(lo, hi, exact=exact)
    except Exception:
        return R.TOP


def _aval_top(aval) -> AbsVal:
    try:
        return R.dtype_top(aval.dtype)
    except Exception:
        return R.TOP


def _is_float(v) -> bool:
    try:
        return np.dtype(v.aval.dtype).kind == "f"
    except Exception:
        return False


def _is_int(v) -> bool:
    try:
        return np.dtype(v.aval.dtype).kind in "iu"
    except Exception:
        return False


def headroom_bits(capacity: float, need: float) -> int:
    """Whole powers of two between the worst-case sum and the dtype limit."""
    if need <= 0:
        return int(capacity).bit_length()
    if need > capacity:
        return 0
    return int(math.floor(math.log2(capacity / need)))


class _Interp:
    def __init__(self, *, axis_sizes=None, cell="", rules=None):
        self.axis_sizes = dict(axis_sizes or {})
        self.cell = cell
        self.rules = frozenset(rules if rules is not None
                               else ("overflow", "numerics"))
        self.findings: dict[tuple, Finding] = {}
        self.proofs: list[dict] = []
        self._proof_sites: set = set()

    # -- findings --------------------------------------------------------
    def _emit(self, rule, severity, message, eqn):
        if rule.split(".")[0] not in self.rules:
            return
        key, where = source_key(eqn.source_info)
        ident = (rule, key, where)
        if ident not in self.findings:
            self.findings[ident] = Finding(
                rule=rule, severity=severity, message=message, key=key,
                where=where, cell=self.cell)

    # -- env helpers -----------------------------------------------------
    def _read(self, v, sc: _Scope) -> AbsVal:
        if not _is_var(v):
            return _literal_val(v.val)
        got = sc.env.get(v)
        if got is None:
            got = _aval_top(v.aval)
            sc.env[v] = got
        return got

    def _origin(self, v, sc: _Scope):
        """Chase a var back through shape-only ops to its producing value."""
        seen = 0
        while _is_var(v) and seen < 128:
            seen += 1
            eqn = sc.producer.get(v)
            if eqn is None:
                nxt = sc.alias.get(v)
                if nxt is None:
                    return v
                v = nxt
                continue
            name = eqn.primitive.name.replace("-", "_")
            if name in _PASSTHROUGH or name == "convert_element_type":
                v = eqn.invars[0]
                continue
            nxt = sc.alias.get(v)
            if nxt is not None and nxt is not v:
                v = nxt
                continue
            return v
        return v

    def _max_dominators(self, v, sc: _Scope) -> set:
        """Origins ``x`` with ``v >= x`` elementwise (maybe via a row max).

        Walks value-preserving ops, ``reduce_max``/``pmax``, and BOTH
        operands of ``max`` (``max(a, b) >= a`` and ``>= b`` — the online-
        softmax carry ``m_new = max(m, rowmax(s))`` needs the two-var
        branch; ``jnp.max`` alone inserts ``max(-inf, reduce_max(x))``).
        Every hop keeps the invariant *chased value >= walked var*.  A
        terminal var (no producer) dominates itself: ``m - max(m, ...)``
        proves ``<= 0`` by reaching ``m`` directly, no reduce_max needed.
        """
        out, work, visited = set(), [v], set()
        while work and len(visited) < 256:
            v = work.pop()
            if not _is_var(v) or v in visited:
                continue
            visited.add(v)
            eqn = sc.producer.get(v)
            if eqn is None:
                nxt = sc.alias.get(v)
                if nxt is not None and nxt is not v:
                    work.append(nxt)
                else:
                    out.add(v)
                continue
            name = eqn.primitive.name.replace("-", "_")
            if name in _PASSTHROUGH or name == "convert_element_type":
                work.append(eqn.invars[0])
                continue
            if name == "max":
                work.extend(iv for iv in eqn.invars if _is_var(iv))
                continue
            if name == "pmax":
                # cross-shard max of a local max still bounds the local
                # values below: keep walking toward the reduce_max
                work.append(eqn.invars[0])
                continue
            if name == "reduce_max":
                out.add(self._origin(eqn.invars[0], sc))
                continue
            nxt = sc.alias.get(v)
            if nxt is not None and nxt is not v:
                work.append(nxt)
        return out

    # -- interpretation --------------------------------------------------
    def run(self, jaxpr, in_vals, const_vals=None) -> list[AbsVal]:
        """Walk ``jaxpr`` in a fresh scope (top level, loop bodies)."""
        return self._run_in(jaxpr, in_vals, _Scope(), const_vals)

    def _run_in(self, jaxpr, in_vals, sc: _Scope, const_vals=None,
                alias_from=None) -> list[AbsVal]:
        for v, val in zip(jaxpr.invars, in_vals):
            sc.env[v] = val if val is not None else _aval_top(v.aval)
        if alias_from is not None:
            for sv, ov in zip(jaxpr.invars, alias_from):
                if _is_var(ov) or not hasattr(ov, "aval"):
                    sc.alias[sv] = ov
        consts = const_vals or []
        for i, v in enumerate(jaxpr.constvars):
            sc.env[v] = consts[i] if i < len(consts) else _aval_top(v.aval)
        for eqn in jaxpr.eqns:
            outs = self._eqn(eqn, sc)
            for v, val in zip(eqn.outvars, outs):
                sc.env[v] = val
                sc.producer[v] = eqn
        return [self._read(v, sc) for v in jaxpr.outvars]

    def _tops(self, eqn) -> list[AbsVal]:
        return [_aval_top(v.aval) for v in eqn.outvars]

    def _eqn(self, eqn, sc: _Scope) -> list[AbsVal]:
        prim = eqn.primitive.name.replace("-", "_")
        vals = [self._read(v, sc) for v in eqn.invars]

        # -- structured control flow & sub-jaxprs ------------------------
        if prim == "scan":
            return self._scan(eqn, vals)
        if prim == "while":
            return self._while(eqn, vals)
        if prim == "cond":
            return self._cond(eqn, vals)
        subs = _jaxpr_params(eqn)
        if subs:
            # jit / shard_map / remat / custom_*: inline into the SAME
            # scope with invar aliases so provenance (guards, max-sub)
            # survives the wrapper jnp.where/jnp.clip emit around bodies
            out = None
            for _, sj in subs:
                sub = _inner(sj)
                if len(sub.invars) == len(eqn.invars):
                    res = self._run_in(sub, vals, sc, alias_from=eqn.invars)
                    if len(res) == len(eqn.outvars):
                        for sv, ov in zip(sub.outvars, eqn.outvars):
                            if _is_var(sv):
                                if sv in sc.maxsub:
                                    sc.maxsub.add(ov)
                                if sv in sc.attains_one:
                                    sc.attains_one.add(ov)
                                sc.alias[ov] = sv
                        res = [R.join(a, b) for a, b in zip(out, res)] \
                            if out is not None else res
                        out = res
            return out if out is not None else self._tops(eqn)

        handler = getattr(self, "_p_" + prim, None)
        if handler is not None:
            out = handler(eqn, vals, sc)
            return out if isinstance(out, list) else [out]
        if prim in _PASSTHROUGH:
            self._propagate_marks(eqn, sc)
            return [vals[0] for _ in eqn.outvars]
        if prim in _SUBSET:
            return [vals[0] for _ in eqn.outvars]
        if prim in _BOUNDED_UNARY:
            lo, hi = _BOUNDED_UNARY[prim]
            return [R.meet_interval(R.TOP, lo, hi)]
        return self._tops(eqn)

    def _propagate_marks(self, eqn, sc: _Scope):
        if eqn.invars and _is_var(eqn.invars[0]):
            src = eqn.invars[0]
            if src in sc.maxsub:
                sc.maxsub.update(eqn.outvars)
            if src in sc.attains_one:
                sc.attains_one.update(eqn.outvars)

    # ================= structured control flow =========================
    def _scan(self, eqn, vals):
        body = _inner(eqn.params["jaxpr"])
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        consts, carry, xs = vals[:nc], vals[nc:nc + ncar], vals[nc + ncar:]
        res = self.run(body, consts + carry + xs)
        for it in range(_MAX_FIX_ITERS):
            new = [R.join(c, o) for c, o in zip(carry, res[:ncar])]
            if it >= 2:
                new = [R.widen(c, n) for c, n in zip(carry, new)]
            if new == carry:
                break
            carry = new
            res = self.run(body, consts + carry + xs)
        return res

    def _while(self, eqn, vals):
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        cond = _inner(eqn.params["cond_jaxpr"])
        body = _inner(eqn.params["body_jaxpr"])
        cconsts, bconsts = vals[:cn], vals[cn:cn + bn]
        carry = vals[cn + bn:]
        res = carry
        for it in range(_MAX_FIX_ITERS):
            out = self.run(body, bconsts + carry)
            new = [R.join(c, o) for c, o in zip(carry, out)]
            if it >= 2:
                new = [R.widen(c, n) for c, n in zip(carry, new)]
            if new == carry:
                res = new
                break
            carry = new
            res = new
        # walk the cond jaxpr too: its numerics findings are real code
        self.run(cond, cconsts + list(res))
        return list(res)

    def _cond(self, eqn, vals):
        out = None
        for br in eqn.params["branches"]:
            res = self.run(_inner(br), vals[1:])
            out = res if out is None else [R.join(a, b)
                                           for a, b in zip(out, res)]
        return out if out is not None else self._tops(eqn)

    # ================= collectives =====================================
    def _axis_prod(self, axes) -> int:
        if axes is None:
            return 1
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        n = 1
        for a in axes:
            n *= int(self.axis_sizes.get(a, 1))
        return n

    def _psum_like(self, eqn, vals, *, kind: str) -> list[AbsVal]:
        n = self._axis_prod(eqn.params.get("axes", ()))
        outs = []
        for v, val in zip(eqn.invars, vals):
            summed = R.scale_by_count(val, n)
            if n > 1 and _is_var(v) and _is_int(v):
                self._check_int_accumulator(eqn, v, val, summed, n, kind)
            outs.append(summed)
        return outs

    def _check_int_accumulator(self, eqn, v, val, summed, n, kind):
        if "overflow" not in self.rules:
            return
        dt = np.dtype(v.aval.dtype)
        info = np.iinfo(dt)
        cap_hi, cap_lo = float(info.max), float(info.min)
        top = R.dtype_top(dt)
        need = summed.mag
        ok = summed.hi <= cap_hi and summed.lo >= cap_lo
        key, where = source_key(eqn.source_info)
        site = (kind, key, where, dt.name, n)
        if site not in self._proof_sites:
            self._proof_sites.add(site)
            self.proofs.append({
                "kind": kind, "dtype": dt.name, "n": n,
                "bound": None if not val.bounded else val.mag,
                "worst_sum": None if need == INF else need,
                "capacity": cap_hi,
                "headroom_bits": headroom_bits(cap_hi, need) if ok else 0,
                "ok": bool(ok), "key": key, "where": where,
            })
        if ok:
            return
        if val.lo <= top.lo and val.hi >= top.hi:
            msg = (f"{kind} over n={n} shards accumulates {dt.name} values "
                   "with no provable bound (no clamp upstream): the integer "
                   "sum cannot be proven to fit the accumulator")
        else:
            msg = (f"{kind} over n={n} shards of {dt.name} values in "
                   f"[{val.lo:g}, {val.hi:g}] sums to ±{need:g} > "
                   f"{dt.name} capacity {cap_hi:g}: the reduction wraps "
                   "on the wire")
        self._emit("overflow.wire_accumulator", "error", msg, eqn)

    def _p_psum(self, eqn, vals, sc):
        return self._psum_like(eqn, vals, kind="psum")

    _p_psum2 = _p_psum_invariant = _p_psum

    def _p_psum_scatter(self, eqn, vals, sc):
        return self._psum_like(eqn, vals, kind="reduce-scatter")

    _p_reduce_scatter = _p_psum_scatter

    def _p_pmax(self, eqn, vals, sc):
        return list(vals)

    _p_pmin = _p_ppermute = _p_all_to_all = _p_pmax

    def _p_all_gather(self, eqn, vals, sc):
        self._propagate_marks(eqn, sc)
        return list(vals)

    def _p_axis_index(self, eqn, vals, sc):
        n = self._axis_prod(eqn.params.get("axis_name", ()))
        return AbsVal(0.0, float(max(n - 1, 0)), exact=True)

    # ================= arithmetic ======================================
    def _p_add(self, eqn, vals, sc):
        return R.add(vals[0], vals[1])

    def _p_sub(self, eqn, vals, sc):
        out = R.sub(vals[0], vals[1])
        # max-subtraction idiom: x - max(x) <= 0 elementwise
        if _is_var(eqn.invars[1]):
            doms = self._max_dominators(eqn.invars[1], sc)
            if doms and self._origin(eqn.invars[0], sc) in doms:
                out = R.meet_interval(out, -INF, 0.0)
                sc.maxsub.update(eqn.outvars)
        return out

    def _p_mul(self, eqn, vals, sc):
        a, b = eqn.invars[0], eqn.invars[1]
        out = R.mul(vals[0], vals[1])
        if (_is_var(a) and _is_var(b)
                and self._origin(a, sc) == self._origin(b, sc)):
            out = R.meet_interval(out, 0.0, INF)    # x * x is a square
        return out

    def _p_div(self, eqn, vals, sc):
        den = vals[1]
        if den.contains(0.0):
            self._emit(
                "numerics.unguarded", "warn",
                f"div by interval {den} containing 0 with no positive guard "
                "upstream (clamp / where(x > 0, ...) / +eps would bound it)",
                eqn)
        return R.div(vals[0], den)

    def _p_neg(self, eqn, vals, sc):
        return R.neg(vals[0])

    def _p_abs(self, eqn, vals, sc):
        return R.abs_(vals[0])

    def _p_max(self, eqn, vals, sc):
        return R.max_(vals[0], vals[1])

    def _p_min(self, eqn, vals, sc):
        return R.min_(vals[0], vals[1])

    def _p_clamp(self, eqn, vals, sc):
        return R.clamp(vals[0], vals[1], vals[2])

    def _p_exp(self, eqn, vals, sc):
        v = vals[0]
        if _is_var(eqn.invars[0]) and eqn.invars[0] in sc.maxsub:
            v = R.meet_interval(v, -INF, 0.0)
            out = R.exp(v)
            sc.attains_one.update(eqn.outvars)   # exp(0) = 1 is attained
            return out
        if v.hi == INF and _is_float(eqn.invars[0]):
            self._emit(
                "numerics.unguarded", "warn",
                f"exp of unbounded interval {v} overflows to inf for "
                "moderate inputs; subtract the running max (softmax idiom) "
                "or clamp the exponent", eqn)
        return R.exp(v)

    def _p_exp2(self, eqn, vals, sc):
        return R._mono(lambda x: 2.0 ** min(x, 4000.0), vals[0])

    def _p_log(self, eqn, vals, sc):
        v = vals[0]
        if v.lo <= 0 and _is_float(eqn.invars[0]):
            self._emit(
                "numerics.unguarded", "warn",
                f"log of interval {v} whose domain includes <= 0 with no "
                "guard upstream (max(x, eps) or the logsumexp idiom would "
                "bound it)", eqn)
        return R.log(v)

    def _p_log1p(self, eqn, vals, sc):
        v = vals[0]
        if v.lo <= -1 and _is_float(eqn.invars[0]):
            self._emit(
                "numerics.unguarded", "warn",
                f"log1p of interval {v} reaching <= -1 with no guard "
                "upstream", eqn)
        return R.log1p(v)

    def _p_sqrt(self, eqn, vals, sc):
        v = vals[0]
        if v.lo < 0 and _is_float(eqn.invars[0]):
            self._emit(
                "numerics.unguarded", "warn",
                f"sqrt of interval {v} reaching below 0 (NaN) with no "
                "clamp upstream", eqn)
        return R.sqrt(v)

    def _p_rsqrt(self, eqn, vals, sc):
        v = vals[0]
        if v.lo <= 0 and _is_float(eqn.invars[0]):
            self._emit(
                "numerics.unguarded", "warn",
                f"rsqrt of interval {v} whose domain includes <= 0 with no "
                "+eps guard upstream (rmsnorm-style `rsqrt(mean(x^2)+eps)` "
                "is the provable form)", eqn)
        return R.rsqrt(v)

    def _p_integer_pow(self, eqn, vals, sc):
        return R.integer_pow(vals[0], eqn.params.get("y", 1))

    def _p_square(self, eqn, vals, sc):
        return R.integer_pow(vals[0], 2)

    def _p_pow(self, eqn, vals, sc):
        a, b = vals
        if a.lo > 0 and a.bounded and b.bounded:
            cands = []
            for x in (a.lo, a.hi):
                for y in (b.lo, b.hi):
                    try:
                        cands.append(x ** y)
                    except OverflowError:
                        cands.append(INF)
            return AbsVal(min(cands), max(cands))
        return R.TOP

    def _p_floor(self, eqn, vals, sc):
        return R.round_family(vals[0], max_delta=1.0)

    def _p_ceil(self, eqn, vals, sc):
        return R.round_family(vals[0], max_delta=1.0)

    def _p_round(self, eqn, vals, sc):
        return R.round_family(vals[0], max_delta=0.5)

    def _p_sign(self, eqn, vals, sc):
        return AbsVal(-1.0, 1.0, exact=True)

    def _p_nextafter(self, eqn, vals, sc):
        return R.join(vals[0], vals[1])

    # ================= conversions / shape / structure =================
    def _p_convert_element_type(self, eqn, vals, sc):
        self._propagate_marks(eqn, sc)
        v = vals[0]
        dt = np.dtype(eqn.params["new_dtype"])
        if dt.kind == "b":
            return R.BOOL
        if dt.kind in "iu":
            src_int = _is_int(eqn.invars[0])
            conv = v if src_int else R.to_integer(v)
            info = np.iinfo(dt)
            if conv.lo < info.min or conv.hi > info.max:
                return R.dtype_top(dt)       # narrowing wraps: all bets off
            return conv
        # float target: integer exactness survives while the mantissa holds
        if v.exact:
            try:
                nmant = np.finfo(dt).nmant
            except ValueError:            # ml_dtypes (bf16/f8) float types
                import ml_dtypes

                nmant = ml_dtypes.finfo(dt).nmant
            if v.mag > 2.0 ** nmant:
                return AbsVal(v.lo, v.hi, exact=False, qerr=v.qerr)
        return v

    def _p_bitcast_convert_type(self, eqn, vals, sc):
        return R.dtype_top(eqn.params["new_dtype"])

    def _p_iota(self, eqn, vals, sc):
        shape = eqn.params.get("shape", ())
        dim = eqn.params.get("dimension", 0)
        n = int(shape[dim]) if shape else 1
        return AbsVal(0.0, float(max(n - 1, 0)), exact=True)

    def _p_concatenate(self, eqn, vals, sc):
        out = vals[0]
        for v in vals[1:]:
            out = R.join(out, v)
        return out

    def _p_pad(self, eqn, vals, sc):
        return R.join(vals[0], vals[1])

    def _p_select_n(self, eqn, vals, sc):
        pred_v, cases = eqn.invars[0], eqn.invars[1:]
        # NaN-propagation selects (`where(x != x, nan_path, y)`): intervals
        # bound the real-valued elements, for which the is-NaN branch is
        # vacuous — keep the other branch instead of joining in its top
        if _is_var(pred_v) and len(cases) == 2:
            porigin = self._origin(pred_v, sc)
            prod = sc.producer.get(porigin) if _is_var(porigin) else None
            if prod is not None and prod.primitive.name in ("ne", "eq"):
                x, y = prod.invars
                if (_is_var(x) and _is_var(y)
                        and self._origin(x, sc) == self._origin(y, sc)):
                    return vals[1] if prod.primitive.name == "ne" else vals[2]
        out = None
        for i, (cv, cval) in enumerate(zip(cases, vals[1:])):
            refined = self._refine_case(pred_v, cv, cval, taken=bool(i), sc=sc)
            out = refined if out is None else R.join(out, refined)
        return out if out is not None else self._tops(eqn)[0]

    def _refine_case(self, pred, case_var, case_val, *, taken, sc) -> AbsVal:
        """Narrow a select_n branch with its comparison predicate.

        For ``select_n(x > k, f, t)`` the ``t`` branch only sees ``x > k``:
        when the branch value IS ``x``, meet its interval with the
        half-line.  ``taken=False`` refines with the negated predicate.
        """
        if not _is_var(pred) or not _is_var(case_var):
            return case_val
        porigin = self._origin(pred, sc)
        if not _is_var(porigin):
            return case_val
        prod = sc.producer.get(porigin)
        if prod is None or prod.primitive.name not in ("gt", "ge", "lt", "le"):
            return case_val
        op = prod.primitive.name
        x, y = prod.invars
        corigin = self._origin(case_var, sc)
        if _is_var(x) and self._origin(x, sc) == corigin:
            kside = self._read(y, sc)
        elif _is_var(y) and self._origin(y, sc) == corigin:
            kside = self._read(x, sc)
            op = {"gt": "lt", "ge": "le", "lt": "gt", "le": "ge"}[op]
        else:
            return case_val
        if kside.lo != kside.hi:
            return case_val
        kval = kside.lo
        if not taken:
            op = {"gt": "le", "ge": "lt", "lt": "ge", "le": "gt"}[op]
        eps_up = float(np.nextafter(kval, np.inf))
        eps_dn = float(np.nextafter(kval, -np.inf))
        if op == "gt":
            return R.meet_interval(case_val, eps_up, INF)
        if op == "ge":
            return R.meet_interval(case_val, kval, INF)
        if op == "lt":
            return R.meet_interval(case_val, -INF, eps_dn)
        return R.meet_interval(case_val, -INF, kval)

    def _p_dynamic_update_slice(self, eqn, vals, sc):
        return R.join(vals[0], vals[1])

    def _p_scatter(self, eqn, vals, sc):
        return R.join(vals[0], vals[-1])

    _p_scatter_max = _p_scatter_min = _p_scatter

    def _p_scatter_add(self, eqn, vals, sc):
        # worst case: every update lands on one element of the operand
        upd = vals[-1]
        try:
            n = int(np.prod(eqn.invars[-1].aval.shape))
        except Exception:
            return self._tops(eqn)[0]
        return R.add(vals[0],
                     R.scale_by_count(R.join(R.point(0.0), upd), n))

    # ================= reductions ======================================
    def _reduced_count(self, eqn) -> int:
        try:
            inn = int(np.prod(eqn.invars[0].aval.shape))
            out = max(int(np.prod(eqn.outvars[0].aval.shape)), 1)
            return max(inn // out, 1)
        except Exception:
            return 1

    def _p_reduce_sum(self, eqn, vals, sc):
        out = R.scale_by_count(vals[0], self._reduced_count(eqn))
        src = eqn.invars[0]
        if (_is_var(src) and src in sc.attains_one and vals[0].lo >= 0.0):
            # the array provably contains an element == 1 and none negative
            out = R.meet_interval(out, 1.0, INF)
        return out

    def _p_reduce_max(self, eqn, vals, sc):
        out = vals[0]
        src = eqn.invars[0]
        if _is_var(src) and src in sc.attains_one:
            out = R.meet_interval(out, 1.0, INF)
        return out

    def _p_reduce_min(self, eqn, vals, sc):
        return vals[0]

    def _p_reduce_and(self, eqn, vals, sc):
        return R.BOOL

    _p_reduce_or = _p_reduce_and

    def _p_cumsum(self, eqn, vals, sc):
        try:
            n = int(eqn.invars[0].aval.shape[eqn.params.get("axis", 0)])
        except Exception:
            n = 1
        return R.scale_by_count(vals[0], n)

    def _p_cummax(self, eqn, vals, sc):
        return vals[0]

    _p_cummin = _p_cummax

    def _p_argmax(self, eqn, vals, sc):
        return AbsVal(0.0, float(max(self._reduced_count(eqn) - 1, 0)),
                      exact=True)

    _p_argmin = _p_argmax

    def _p_dot_general(self, eqn, vals, sc):
        try:
            (lc, _), _ = eqn.params["dimension_numbers"]
            lshape = eqn.invars[0].aval.shape
            k = 1
            for d in lc:
                k *= int(lshape[d])
        except Exception:
            k = 1
        return R.scale_by_count(R.mul(vals[0], vals[1]), k)

    def _p_sort(self, eqn, vals, sc):
        return list(vals)

    def _p_is_finite(self, eqn, vals, sc):
        return R.BOOL

    def _p_eq(self, eqn, vals, sc):
        return R.BOOL

    _p_ne = _p_lt = _p_le = _p_gt = _p_ge = _p_eq
    _p_and = _p_or = _p_xor = _p_not = _p_eq


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def abstract_eval(closed_jaxpr, in_vals=None, *, axis_sizes=None,
                  rules=()) -> list[AbsVal]:
    """Propagate AbsVals through ``closed_jaxpr``; returns per-outvar values.

    ``in_vals``: one AbsVal per invar (None entries default to the dtype
    top).  With ``rules=()`` this is a pure evaluator — the form the
    soundness property tests drive.
    """
    return interpret_jaxpr(closed_jaxpr, in_vals=in_vals,
                           axis_sizes=axis_sizes, rules=rules).out


def interpret_jaxpr(closed_jaxpr, *, in_vals=None, axis_sizes=None, cell="",
                    rules=("overflow", "numerics")) -> AbsintResult:
    """Interpret one traced step; returns findings + proofs + out values."""
    jaxpr = _inner(closed_jaxpr)
    interp = _Interp(axis_sizes=axis_sizes, cell=cell, rules=rules)
    if in_vals is None:
        in_vals = [None] * len(jaxpr.invars)
    const_vals = [_literal_val(c) for c in
                  getattr(closed_jaxpr, "consts", None) or []]
    out = interp.run(jaxpr, list(in_vals), const_vals)
    return AbsintResult(findings=list(interp.findings.values()),
                        proofs=interp.proofs, out=out)
