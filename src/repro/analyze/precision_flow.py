"""Precision-flow lint: a taint walk over traced jaxprs.

TAINT SOURCES are quantized-code arrays: integer leaves of itemsize <= 2
(int8/int16 QTensor codes) with rank >= 2 — token ids, page tables and
lengths are int32/rank-1 and never taint.  Taint PROPAGATES through the
dequantization idiom (``convert_element_type``, ``mul`` by a scale,
reshapes/transposes/slices, FSDP ``all_gather``) and STOPS with a finding
at any ``dot_general`` consuming a tainted operand: that matmul read a
weight that was eagerly dequantized to floats in HBM instead of streaming
codes through the ``quant_matmul`` Pallas kernel — the exact silent
fallback that erases the paper's storage/bandwidth win (arXiv 2012.11070).

Taint deliberately does NOT propagate through ``gather``/``take`` (the
embedding-row read is a lookup, not a matmul weight) nor through ``add``
(residual streams would smear taint over the whole graph).

The walk also checks integer ``psum`` accumulators: summing ``n`` clients'
``bits``-wide codes needs the dtype of ``n * (2^bits - 1)``
(:func:`repro.dist.collectives.wire_dtype`); anything narrower overflows
on the wire.

Sub-jaxprs (scan/while/cond/jit/shard_map/remat/custom_*) are entered
with taint mapped across their invars; loop carries iterate to a fixpoint
before findings are collected, so a dequant inside a scanned layer body is
reported exactly once.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.analyze.findings import Finding, source_key

# primitives the dequant dataflow can pass through without changing what
# the values ARE (codes, possibly scaled)
_PROPAGATE = frozenset({
    "convert_element_type", "mul", "div", "broadcast_in_dim", "transpose",
    "reshape", "squeeze", "expand_dims", "slice", "dynamic_slice",
    "all_gather", "copy", "rev", "concatenate", "pad", "stop_gradient",
    "optimization_barrier",
})

def _is_var(v) -> bool:
    """True for jaxpr Vars (hashable); Literals carry ``.val``."""
    return hasattr(v, "aval") and not hasattr(v, "val")


def _is_code_like(aval) -> bool:
    try:
        return (jnp.issubdtype(aval.dtype, jnp.integer)
                and aval.dtype.itemsize <= 2 and aval.ndim >= 2)
    except Exception:
        return False


def _inner(j):
    """Jaxpr from either a ClosedJaxpr or a raw Jaxpr."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _jaxpr_params(eqn):
    """(param_name, jaxpr-ish) pairs found in an eqn's params."""
    out = []
    for k, v in eqn.params.items():
        if hasattr(v, "eqns") or (hasattr(v, "jaxpr")
                                  and hasattr(v.jaxpr, "eqns")):
            out.append((k, v))
        elif isinstance(v, (tuple, list)):
            for vi in v:
                if hasattr(vi, "eqns") or (hasattr(vi, "jaxpr")
                                           and hasattr(vi.jaxpr, "eqns")):
                    out.append((k, vi))
    return out


class _Walker:
    def __init__(self, *, policy, axis_sizes, cell, collect):
        self.policy = policy
        self.axis_sizes = dict(axis_sizes or {})
        self.cell = cell
        self.collect = collect
        self.findings: dict[tuple, Finding] = {}
        self.n_dots = 0
        self.n_fastpath = 0

    # -- finding helpers -------------------------------------------------
    def _emit(self, rule, severity, message, key, where):
        if not self.collect:
            return
        ident = (rule, key, where)
        if ident not in self.findings:
            self.findings[ident] = Finding(
                rule=rule, severity=severity, message=message, key=key,
                where=where, cell=self.cell)

    # -- the walk --------------------------------------------------------
    def run(self, jaxpr, in_taint):
        """Walk one (raw) jaxpr; returns per-outvar taint flags."""
        tainted = set()
        for v, t in zip(jaxpr.invars, in_taint):
            if t:
                tainted.add(v)
        for v in jaxpr.constvars:
            if _is_code_like(v.aval):
                tainted.add(v)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, tainted)
        out = []
        for v in jaxpr.outvars:
            out.append(_is_var(v) and v in tainted)
        return out

    def _taint_of(self, eqn, tainted):
        return [_is_var(v) and v in tainted for v in eqn.invars]

    def _eqn(self, eqn, tainted):
        prim = eqn.primitive.name
        in_taint = self._taint_of(eqn, tainted)

        if prim == "pallas_call":
            # the fast path itself: codes are consumed INSIDE the kernel
            if eqn.params.get("name") == "quant_matmul":
                self.n_fastpath += 1
            return

        if prim in ("dot_general", "conv_general_dilated"):
            self.n_dots += 1
            if any(in_taint):
                key, where = source_key(eqn.source_info)
                operand = "lhs" if in_taint[0] else "rhs"
                shapes = [tuple(v.aval.shape) for v in eqn.invars
                          if hasattr(v, "aval")]
                sev = "error" if self.policy.lazy else "info"
                self._emit(
                    "precision.eager_dequant", sev,
                    f"{prim} {operand} consumes eagerly-dequantized QTensor "
                    f"codes (shapes {shapes}); the quant_matmul fast path "
                    "streams codes instead", key, where)
            return                              # dot output is activations

        if prim in ("psum", "psum2", "psum_invariant"):
            self._check_psum(eqn, tainted)
            if any(in_taint):
                for v in eqn.outvars:
                    tainted.add(v)
            return

        if prim in ("gather", "take", "dynamic_gather"):
            return                              # embedding-row reads

        subs = _jaxpr_params(eqn)
        if subs:
            self._sub(eqn, subs, in_taint, tainted)
            return

        if prim in _PROPAGATE and any(in_taint):
            for v in eqn.outvars:
                tainted.add(v)

    def _check_psum(self, eqn, tainted):
        from repro.dist.collectives import wire_dtype

        bits = getattr(self.policy, "comm", 32)
        if bits >= 32:
            return
        axes = eqn.params.get("axes", ())
        n = 1
        for a in axes:
            n *= int(self.axis_sizes.get(a, 1))
        if n <= 1:
            return
        try:
            required = jnp.dtype(wire_dtype(bits, n))
        except Exception:
            return
        for v in eqn.invars:
            if not hasattr(v, "aval"):
                continue
            dt = v.aval.dtype
            if jnp.issubdtype(dt, jnp.integer) and dt.itemsize < required.itemsize:
                key, where = source_key(eqn.source_info)
                self._emit(
                    "precision.narrow_accumulator", "error",
                    f"psum over {axes} (n={n}) accumulates {dt.name} codes "
                    f"but n*(2^{bits}-1) needs {required.name}: the "
                    "reduction overflows on the wire", key, where)

    def _sub(self, eqn, subs, in_taint, tainted):
        prim = eqn.primitive.name
        out_taint = [False] * len(eqn.outvars)

        if prim == "while":
            cn = int(eqn.params.get("cond_nconsts", 0))
            bn = int(eqn.params.get("body_nconsts", 0))
            body = _inner(eqn.params["body_jaxpr"])
            body_in = in_taint[cn:]             # body consts + carry
            carry_in = body_in[bn:]
            for _ in range(3):                  # taint fixpoint over carry
                res = self.run(body, body_in)
                new_carry = [a or b for a, b in zip(carry_in, res)]
                if new_carry == carry_in:
                    break
                carry_in = new_carry
                body_in = body_in[:bn] + carry_in
            out_taint = carry_in
        elif prim == "scan":
            sub = _inner(eqn.params["jaxpr"])
            nc = int(eqn.params.get("num_consts", 0))
            ncar = int(eqn.params.get("num_carry", 0))
            sub_in = list(in_taint)
            for _ in range(3):
                res = self.run(sub, sub_in)
                new_carry = [a or b
                             for a, b in zip(sub_in[nc:nc + ncar], res[:ncar])]
                if new_carry == sub_in[nc:nc + ncar]:
                    out_taint = res
                    break
                sub_in[nc:nc + ncar] = new_carry
            else:
                out_taint = res
        elif prim == "cond":
            for _, br in subs:
                res = self.run(_inner(br), in_taint[1:])
                out_taint = [a or b for a, b in zip(out_taint, res)]
        else:
            # jit / shard_map / remat / custom_* and any unknown primitive
            # whose sub-jaxpr invars align 1:1 with the eqn's
            for _, sj in subs:
                sub = _inner(sj)
                if len(sub.invars) == len(eqn.invars):
                    res = self.run(sub, in_taint)
                    out_taint = [a or b for a, b in zip(out_taint, res)]
                # non-aligned unknown sub-jaxpr: skip (conservative: its
                # outputs are treated as untainted)

        for v, t in zip(eqn.outvars, out_taint):
            if t:
                tainted.add(v)


def lint_jaxpr(closed_jaxpr, *, policy, axis_sizes=None, cell="",
               expect_fastpath=None) -> list[Finding]:
    """Precision-flow lint over one traced step's ClosedJaxpr.

    ``axis_sizes``: mesh axis name -> size (for the psum accumulator rule).
    ``expect_fastpath``: when True (default: ``policy.lazy``), a module
    that contains matmuls but not one ``quant_matmul`` pallas_call gets a
    ``precision.no_fastpath`` warning — the wholesale-dispatch-loss guard.
    """
    w = _Walker(policy=policy, axis_sizes=axis_sizes, cell=cell,
                collect=True)
    jaxpr = _inner(closed_jaxpr)
    in_taint = [_is_code_like(v.aval) for v in jaxpr.invars]
    w.run(jaxpr, in_taint)
    findings = list(w.findings.values())
    expect = policy.lazy if expect_fastpath is None else expect_fastpath
    if expect and w.n_dots > 0 and w.n_fastpath == 0:
        findings.append(Finding(
            rule="precision.no_fastpath", severity="warn",
            message=f"policy is lazy but none of the {w.n_dots} matmuls "
                    "went through the quant_matmul kernel — dispatch lost "
                    "wholesale?",
            key="module:no_fastpath", cell=cell))
    return findings
