"""Session: the one front door for every workload.

``Session(RunSpec(...))`` owns the mesh, :class:`AxisCtx`, model,
``ParamCtx`` construction, and checkpoint manager, and exposes the five
workload kinds behind one API::

    from repro.api import PrecisionPolicy, RunSpec, Session

    stats = Session(RunSpec("yi-6b", workload="serve",
                            precision=PrecisionPolicy.lazy_int8())).run()

Per-workload ``options`` keys:

* ``train`` / ``fl-orchestrate`` — ``scheme`` (fl-orchestrate only), ``lr``,
  ``ckpt_dir``, ``out``, ``quiet``.
* ``serve`` — ``steps``, ``s_max``, ``prompt_len``, ``attn_impl``,
  ``requests``, ``max_new``, ``quiet``.
* ``dryrun`` — ``shape``, ``variant`` (gather_bf16 / capacity / no_remat),
  ``out``.
* ``fl-sim`` — ``scheme``, ``n_clients``, ``lr``, ``error_tolerance``,
  ``eval_every``, ``quiet``, ``faults`` (a ``FaultPlan`` dict: deterministic
  fault injection + resilient rounds), ``resolve_drift_db``, ``ckpt_dir``,
  ``ckpt_every``.
* any workload — ``precision_program`` (a :mod:`repro.api.program` kind name
  or config dict): the per-round controller that turns measured state into
  the round's :class:`PrecisionPolicy`.  The default ``constant`` program is
  the identity — it reproduces the static-policy run bitwise.

The ``train`` workload runs federated rounds at the spec's FIXED
:class:`PrecisionPolicy`; ``fl-orchestrate`` is the paper's full loop — every
round the GBD co-design emits a fresh per-device policy
(``PrecisionPolicy.from_gbd``) that drives the same traced-delta train step.
A non-constant ``precision_program`` sits between the two: the program may
clamp the proposed policy round-by-round (energy budget tracking, channel
drift re-solves, paged-KV pool demotion).  Compiled train steps are cached
per compile-relevant policy key, so a schedule that visits K distinct comm
bit-widths costs K compiles, not one per round.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
import time

import numpy as np

from repro.api.precision import PrecisionPolicy
from repro.api.spec import RunSpec, SIM_ARCHS

log = logging.getLogger("repro.api")

BOS_ID = 1


@dataclasses.dataclass
class ServeStats:
    """What one driver run measured (bench_serving / tests consume this)."""

    arch: str
    bits: int
    attn_impl: str
    decode_steps: int
    decoded_tokens: int          # tokens produced by ACTIVE slots only
    completed: int               # sequences finished
    admitted: int                # sequences admitted (>= batch when the
                                 # queue forced mid-flight admissions)
    wall_s: float                # decode-loop wall clock (post-compile)
    tok_s: float
    bytes_per_step_packed: int   # weight bytes streamed per decode step
    bytes_per_step_f32: int      # same weights at f32
    packed_vs_f32: float         # packed / f32 byte ratio
    sample: list                 # first finished sequence's tokens
    kv_layout: str = "contiguous"    # "paged" | "contiguous"
    page_size: int = 0               # tokens per page (0 = contiguous)
    kv_bytes: int = 0                # resident K/V bytes, this layout
    kv_bytes_contiguous: int = 0     # what a contiguous cache would reserve
    capacity_stops: int = 0          # sequences stopped AT CACHE CAPACITY
                                     # (the anti-silent-clip guard firing)
    deferred_admissions: int = 0     # admissions that waited for page reclaim
    prompt_buckets: list = dataclasses.field(default_factory=list)
    kv_demotions: int = 0            # f32 -> bf16 pool casts under pressure
                                     # (precision_program kv_watermark)
    kv_bits_final: int = 0           # KV element bits when the run ended


def _weight_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


class Session:
    """Owns mesh + axes + model + precision plumbing for one RunSpec."""

    def __init__(self, spec: RunSpec):
        self.spec = spec
        self._train_state: dict | None = None

    # -- lazily-built shared structure ----------------------------------
    @functools.cached_property
    def policy(self) -> PrecisionPolicy:
        return self.spec.precision

    @functools.cached_property
    def program(self):
        """The per-round precision controller (``precision_program`` option;
        defaults to the identity ``constant`` program)."""
        from repro.api.program import build_program

        return build_program(self.spec.opt("precision_program"))

    @functools.cached_property
    def cfg(self):
        from repro.configs import get_config, smoke_variant

        if self.spec.arch in SIM_ARCHS:
            raise ValueError(f"{self.spec.arch!r} is an fl-sim architecture; "
                             "the model-zoo config registry does not apply")
        cfg = get_config(self.spec.arch)
        return smoke_variant(cfg) if self.spec.smoke else cfg

    @functools.cached_property
    def model(self):
        from repro.models.model import build_model

        return build_model(self.cfg)

    @functools.cached_property
    def _mesh_and_axes(self):
        from repro.launch.mesh import mesh_and_axes, parse_mesh

        shape, _ = parse_mesh(self.spec.mesh)   # spec errors surface as-is
        if self.spec.workload == "dryrun":
            # AOT lowering needs the full device grid to exist as fake host
            # devices.  XLA reads the flag at backend init, so set it here —
            # before the first device query — rather than relying on the CLI
            # shim's import-time environ write.
            import os

            need = int(np.prod(shape))
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count={need}"
                ).strip()
        try:
            return mesh_and_axes(self.spec.mesh)
        except ValueError as e:
            raise ValueError(
                f"mesh {self.spec.mesh!r} needs more devices than this "
                "process has (jax already initialized its backend?); start a "
                "fresh process or export XLA_FLAGS="
                "--xla_force_host_platform_device_count=<n> first") from e

    @property
    def mesh(self):
        return self._mesh_and_axes[0]

    @property
    def axes(self):
        return self._mesh_and_axes[1]

    @functools.cached_property
    def ckpt(self):
        from repro.ckpt import CheckpointManager

        ckpt_dir = self.spec.opt("ckpt_dir", "")
        every = int(self.spec.opt("ckpt_every", 10))
        return CheckpointManager(ckpt_dir, every=every) if ckpt_dir else None

    def train_config(self):
        from repro.configs.base import TrainConfig

        return TrainConfig(
            learning_rate=float(self.spec.opt("lr", 0.05)),
            seed=self.spec.seed,
            grad_compression_bits=self.policy.grad_compression_bits,
            nonfinite_grads=str(self.spec.opt("nonfinite_grads", "raise")))

    def comm_report(self) -> dict:
        """Bytes-on-wire for gradient reduction on this mesh, per round.

        The flat top-level keys are the BASE policy's one-round accounting
        (the stable contract the analyzer's ``wire.comm_report_mismatch``
        check and the sweep reporter read): replicated leaves move
        ``policy.comm``-bit codes through the SR-quantized all-reduce
        (:func:`repro.dist.collectives.quantized_psum_batch`), FSDP leaves
        reduce-scatter in f32.  Uses the same local parameter template and
        FSDP plan the compiled train step partitions with.

        ``rounds`` adds one row per round with the comm bits that round
        actually used — executed bits once rounds have run, otherwise the
        static schedule (base policy every round) — so an adaptive
        program's mixed-width schedule shows up row by row instead of being
        averaged away.  ``program`` carries the controller's comm envelope
        and the widest wire accumulator any member needs.
        """
        from repro.dist.collectives import envelope_wire_dtype
        from repro.dist.wire import grad_wire_report, grad_wire_rounds
        from repro.launch.mesh import batch_size, fsdp_size
        from repro.launch.steps import local_param_shapes

        tree = local_param_shapes(self.model, self.mesh, self.axes)
        fsdp = fsdp_size(self.mesh, self.axes)
        n = max(batch_size(self.mesh, self.axes), 1)
        rep = grad_wire_report(tree, fsdp=fsdp, n_clients=n,
                               comm_bits=self.policy.comm)
        bits_seq = self._executed_comm_bits()
        if bits_seq is None:
            bits_seq = [int(self.policy.comm)] * max(self.spec.rounds, 1)
        rows = grad_wire_rounds(tree, fsdp=fsdp, n_clients=n,
                                comm_bits_seq=bits_seq)
        rep["rounds"] = rows
        rep["total_bytes_wire"] = int(sum(r["replicated_bytes_wire"]
                                          for r in rows))
        rep["total_bytes_f32"] = int(sum(r["replicated_bytes_f32"]
                                         for r in rows))
        env = self.program.comm_envelope(self.policy)
        dt = envelope_wire_dtype(env, n)
        rep["program"] = {
            "kind": self.program.kind,
            "comm_envelope": [int(b) for b in env],
            "envelope_wire_dtype": (np.dtype(dt).name if dt is not None
                                    else "float32"),
        }
        return rep

    def _executed_comm_bits(self) -> "list[int] | None":
        """Per-round comm bits actually run so far, oldest first (None
        before any round has executed)."""
        st = self._train_state
        if not st:
            return None
        orch = st.get("orch")
        if orch is not None and orch.energy_log:
            return [int(e.get("comm_bits", self.policy.comm))
                    for e in orch.energy_log]
        hist = st.get("history") or []
        if hist and "comm_bits" in hist[0]:
            return [int(h["comm_bits"]) for h in hist]
        return None

    # -- primitive builders ---------------------------------------------
    def init_params(self, key=None):
        import jax

        from repro.launch.steps import build_init_fn

        init_fn, _ = build_init_fn(self.model, self.mesh, self.axes)
        return init_fn(key if key is not None
                       else jax.random.PRNGKey(self.spec.seed))

    @functools.cached_property
    def serving_params(self):
        """The serving weights: :meth:`init_params` packed to the policy's
        storage (norm/router exemptions as in training).

        Init and pack are one jitted program, so each f32 leaf is a
        transient of that program and the full f32 tree is never resident:
        a published-width model whose f32 tree exceeds device memory fits
        once packed.
        """
        import jax

        from repro.core.quantization import default_exempt
        from repro.launch.steps import build_init_fn
        from repro.models.common import pack_params_for_policy

        init_fn, _ = build_init_fn(self.model, self.mesh, self.axes)
        policy = self.policy

        def init_and_pack(key):
            return pack_params_for_policy(init_fn(key), policy,
                                          jax.random.PRNGKey(1),
                                          exempt=default_exempt)

        return jax.jit(init_and_pack)(jax.random.PRNGKey(self.spec.seed))

    def train_step(self, opt=None, *, attn_impl: str = "auto",
                   donate: bool = False):
        """Policy-driven :class:`~repro.launch.steps.TrainStep` builder."""
        from repro.launch.steps import build_train_step
        from repro.optim import build_optimizer

        tc = self.train_config()
        if opt is None:
            opt = build_optimizer("sgd", tc.learning_rate)
        return build_train_step(self.model, self.mesh, self.axes, opt, tc,
                                attn_impl=attn_impl, donate=donate)

    # -- workload dispatch ----------------------------------------------
    def run(self):
        wl = self.spec.workload
        if wl in ("train", "fl-orchestrate"):
            return self.run_train()
        if wl == "serve":
            return self.serve()
        if wl == "dryrun":
            return self.run_dryrun()
        if wl == "fl-sim":
            return self.run_fl_sim()
        raise ValueError(wl)  # unreachable: RunSpec validates

    # ------------------------------------------------------------------
    # train / fl-orchestrate: the pod FWQ-FL loop
    # ------------------------------------------------------------------
    def _ensure_train_state(self) -> dict:
        if self._train_state is not None:
            return self._train_state
        import jax
        import jax.numpy as jnp

        from repro.core.energy import heterogeneous_fleet, memory_capacities
        from repro.data.pipeline import TokenBatcher
        from repro.data.synthetic import SyntheticTokens
        from repro.fed.orchestrator import FLOrchestrator, OrchestratorConfig
        from repro.optim import build_optimizer

        spec, cfg = self.spec, self.cfg
        tc = self.train_config()
        opt = build_optimizer("sgd", tc.learning_rate)
        ts = self.train_step(opt, donate=False)
        n_clients = ts.n_clients
        B = n_clients * spec.batch

        params = self.init_params()
        opt_state = opt.init(params)

        tokens = SyntheticTokens(n_tokens=300_000, vocab=cfg.vocab_size,
                                 seed=spec.seed).generate()
        batcher = TokenBatcher(tokens, spec.seq, seed=spec.seed)

        orch = None
        if spec.workload == "fl-orchestrate":
            fleet = heterogeneous_fleet(n_clients, seed=spec.seed,
                                        group_step_mhz=5.0)
            caps = memory_capacities(n_clients, lo_mb=8, hi_mb=64) * 1e6
            n_params = cfg.param_count()
            orch = FLOrchestrator(
                OrchestratorConfig(n_devices=n_clients, n_rounds=spec.rounds,
                                   scheme=spec.opt("scheme", "fwq"),
                                   model_dim_d=n_params,
                                   precision=self.policy, seed=spec.seed,
                                   faults=spec.opt("faults"),
                                   program=spec.opt("precision_program"),
                                   resolve_drift_db=float(
                                       spec.opt("resolve_drift_db", 0.0))),
                fleet, caps, grad_bytes=4.0 * n_params)

        step = ts.fn(self.model.train_batch_spec(B, spec.seq))
        start = 0
        if self.ckpt:
            expect = None
            if orch is not None:
                expect = {"faults": (orch.cfg.faults.to_dict()
                                     if orch.cfg.faults is not None else None)}
            state, start, _ = self.ckpt.restore_or({"p": params, "o": opt_state},
                                                   expect_extra=expect)
            if start:
                params, opt_state = state["p"], state["o"]
                log.info("resumed at round %d", start)
                if orch is not None:
                    # replay the completed rounds' planning (seeded host
                    # math): rebuilds the solver cadence, fault realizations
                    # and energy log exactly as the uninterrupted run saw
                    # them, so the resumed trajectory is bit-identical
                    for r in range(start):
                        orch.plan_round(r)
                else:
                    # plain train: the session program is the only stateful
                    # planner — replay its (deterministic, observation-
                    # driven) decisions the same way
                    for r in range(start):
                        self.program.policy_for_round(
                            r, self.policy, self._observe_train(r))

        self._train_state = dict(
            jax=jax, jnp=jnp, opt=opt, step=step, params=params,
            opt_state=opt_state, batcher=batcher, orch=orch,
            n_clients=n_clients, B=B, start=start, history=[],
            step_cache={self.policy.grad_compression_bits: step},
            energy_cum=0.0)
        return self._train_state

    def _observe_train(self, r: int):
        """Controller observation for the plain ``train`` workload (no
        orchestrator energy model: cumulative spend is what the history
        rows have recorded, 0.0 before any round runs)."""
        from repro.api.program import Observation

        st = self._train_state or {}
        hist = st.get("history") or []
        return Observation(
            round=r, rounds_total=self.spec.rounds,
            energy_cum_j=float(st.get("energy_cum", 0.0)),
            energy_round_j=float(hist[-1]["energy_j"]) if hist else 0.0)

    def _train_step_for(self, policy: PrecisionPolicy):
        """Compiled train step for ``policy``, cached by its compile-relevant
        key (the gradient wire width — weight bits flow through the traced
        ``delta`` argument, so they never force a retrace).  A K-policy
        schedule therefore costs K compiles, not one per round."""
        from repro.launch.steps import build_train_step

        st = self._ensure_train_state()
        key = policy.grad_compression_bits
        cache = st["step_cache"]
        if key not in cache:
            tc = dataclasses.replace(self.train_config(),
                                     grad_compression_bits=key)
            ts = build_train_step(self.model, self.mesh, self.axes,
                                  st["opt"], tc, donate=False)
            cache[key] = ts.fn(self.model.train_batch_spec(st["B"],
                                                           self.spec.seq))
        return cache[key]

    def fl_round(self, r: int) -> dict:
        """One federated round: per-round policy -> traced delta -> step.

        Under ``fl-orchestrate`` the round's :class:`PrecisionPolicy` comes
        from the GBD co-design (``plan["policy"]``, built via
        ``PrecisionPolicy.from_gbd``); under ``train`` the spec's fixed
        policy applies every round.
        """
        st = self._ensure_train_state()
        jax, jnp = st["jax"], st["jnp"]
        spec, cfg = self.spec, self.cfg
        n_clients, B = st["n_clients"], st["B"]

        plan = st["orch"].plan_round(r) if st["orch"] is not None else None
        if plan is not None:
            # the orchestrator already ran its own program over the GBD
            # proposal — plan["policy"] is the round's final word
            policy = plan["policy"]
        else:
            policy = self.program.policy_for_round(r, self.policy,
                                                   self._observe_train(r))
        bits = policy.bits_vector(n_clients)

        raw = st["batcher"].sample_round(r, n_clients, spec.batch)
        batch = {
            "tokens": jnp.asarray(raw["tokens"].reshape(B, spec.seq)),
            "labels": jnp.asarray(raw["labels"].reshape(B, spec.seq)),
        }
        if cfg.family == "vlm":
            batch["images"] = jnp.zeros((B, cfg.n_image_tokens,
                                         cfg.d_frontend), jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((B, spec.seq, cfg.d_frontend),
                                        jnp.float32)
        delta = policy.delta(n_clients)
        step = self._train_step_for(policy)
        t0 = time.time()
        st["params"], st["opt_state"], m = step(
            st["params"], st["opt_state"], batch, delta,
            jax.random.fold_in(jax.random.PRNGKey(spec.seed), r))
        rec = {"round": r, "loss": float(m["loss"]),
               "bits": bits.tolist(),
               "comm_bits": int(policy.comm),
               "energy_j": plan["energy_round"] if plan else 0.0,
               "t_round_s": plan["t_round"] if plan else 0.0,
               "wall_s": round(time.time() - t0, 3),
               "cohort": int(plan["cohort"].sum()) if plan else n_clients}
        if plan is not None and "retransmissions" in plan:
            rec.update(retransmissions=plan["retransmissions"],
                       retx_energy_j=plan["retx_energy_j"],
                       undelivered=plan["undelivered"],
                       dropped_midround=plan["dropped_midround"])
        st["history"].append(rec)
        st["energy_cum"] += float(rec["energy_j"])
        if self.ckpt:
            extra = {"round": r + 1}
            orch = st["orch"]
            if orch is not None:
                extra["faults"] = (orch.cfg.faults.to_dict()
                                   if orch.cfg.faults is not None else None)
            self.ckpt.maybe_save(r + 1, {"p": st["params"],
                                         "o": st["opt_state"]}, extra=extra)
        return rec

    def run_train(self) -> list[dict]:
        st = self._ensure_train_state()
        quiet = bool(self.spec.opt("quiet", False))
        for r in range(st["start"], self.spec.rounds):
            rec = self.fl_round(r)
            if not quiet:
                log.info("round %d loss=%.4f bits=%s energy=%.2fJ",
                         r, rec["loss"], sorted(set(rec["bits"])),
                         rec["energy_j"])
        history = st["history"]
        total_e = sum(h["energy_j"] for h in history)
        if not quiet and history:
            scheme = (self.spec.opt("scheme", "fwq")
                      if self.spec.workload == "fl-orchestrate" else "fixed")
            print(f"\nscheme={scheme} rounds={len(history)} "
                  f"final_loss={history[-1]['loss']:.4f} "
                  f"total_energy={total_e:.2f}J")
        out = self.spec.opt("out", "")
        if out:
            with open(out, "w") as f:
                json.dump(history, f, indent=1)
        return history

    # ------------------------------------------------------------------
    # serve: continuous-batching quantized decode driver
    # ------------------------------------------------------------------
    def serve(self, **overrides) -> ServeStats:
        """Drive the continuous-batching decode loop; returns ServeStats.

        Weight precision comes from the session policy: ``packed`` policies
        store int8/int16 ``QTensor`` codes, and ``policy.lazy`` keeps them
        packed through the ``quant_matmul`` kernel path.  ``overrides`` patch
        individual options (steps, requests, ...) for this call only.

        KV-cache layout (``kv_layout`` option, default ``"paged"`` where the
        family supports it): the paged layout allocates each request's pages
        ON ADMIT for its full capacity (prompt + max_new, page-rounded) from
        a shared pool sized by ``pool_pages`` (default: the largest
        ``batch`` concurrent requests), reclaims them on completion, and
        DEFERS admissions the pool cannot hold until a completion frees
        pages.  Either layout enforces capacity: a slot whose cache fills up
        is stopped and counted in ``capacity_stops`` instead of silently
        clipping its context.  Prompts are right-padded to power-of-two
        buckets so one compiled prefill serves every prompt length in the
        bucket (``vary_prompt`` draws ragged prompt lengths).
        """
        import jax
        import jax.numpy as jnp

        from repro.launch.paging import (SlotPager, kv_cache_bytes,
                                         pages_for, plan_admissions,
                                         set_page_tables)
        from repro.launch.steps import (
            build_cached_prefill, build_decode_step, init_global_caches)

        spec, policy = self.spec, self.policy
        o = dict(spec.options)
        o.update(overrides)
        steps = int(o.get("steps", 16))
        batch = spec.batch
        s_max = int(o.get("s_max", spec.seq))
        prompt_len = min(int(o.get("prompt_len", 8)), s_max)
        attn_impl = o.get("attn_impl", "ref")
        requests = o.get("requests")
        max_new = o.get("max_new")
        quiet = bool(o.get("quiet", False))
        vary_prompt = bool(o.get("vary_prompt", False))
        seed = spec.seed

        if attn_impl not in ("ref", "flash"):
            raise ValueError(f"attn_impl must be 'ref' or 'flash', "
                             f"got {attn_impl!r}")
        impl = "auto" if attn_impl == "ref" else "flash"

        def say(msg):
            if not quiet:
                print(msg)

        cfg, model, mesh, axes = self.cfg, self.model, self.mesh, self.axes

        # ---- KV layout ---------------------------------------------------
        kv_layout_opt = o.get("kv_layout")
        kv_layout = (kv_layout_opt if kv_layout_opt is not None
                     else "paged" if model.supports_paged_kv
                     else "contiguous")
        if kv_layout not in ("paged", "contiguous"):
            raise ValueError(f"kv_layout must be 'paged' or 'contiguous', "
                             f"got {kv_layout!r}")
        if kv_layout == "paged" and not model.supports_paged_kv:
            kv_layout = "contiguous"    # SSM: O(1) state, nothing to page
        if kv_layout == "paged":
            from repro.launch.mesh import tp_size
            from repro.models.attention import kv_cache_seq_parallel
            from repro.models.transformer import attn_dims

            if kv_cache_seq_parallel(attn_dims(cfg, tp_size(mesh, axes))):
                # the driver's host page allocator covers the kv-sharded /
                # tp=1 layouts; sequence-parallel paged decode is exercised
                # at the step level (build_decode_step).  A defaulted layout
                # falls back so tp>1 kv-replicated serving keeps working;
                # only an EXPLICIT paged request errors.
                if kv_layout_opt is None:
                    kv_layout = "contiguous"
                else:
                    raise ValueError(
                        "kv_layout='paged' is not supported by the serving "
                        "driver on sequence-parallel (kv-replicated, tp>1) "
                        "meshes; drop the option to fall back to contiguous "
                        "or drive build_decode_step directly")
        page_size = o.get("page_size")
        if page_size is None:
            page_size = next(p for p in (16, 8, 4, 2, 1) if s_max % p == 0)
        page_size = int(page_size)

        # ---- weights, packed to the policy's storage ---------------------
        raw = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        raw_bytes = _weight_bytes(raw)
        f32_bytes = sum(x.size * 4 for x in jax.tree_util.tree_leaves(raw))
        serve_bits = policy.serve_bits
        qparams = self.serving_params
        q_bytes = _weight_bytes(qparams)
        if policy.packed:
            say(f"params: {raw_bytes/1e6:.1f} MB f32 -> {q_bytes/1e6:.1f} MB "
                f"packed ({raw_bytes/q_bytes:.2f}x smaller, bits={serve_bits})")
        else:
            say(f"params: {raw_bytes/1e6:.1f} MB f32 (unpacked baseline)")

        # ---- synthetic request queue ------------------------------------
        n_requests = requests if requests is not None else 2 * batch
        rng = np.random.RandomState(seed)
        # default cap: ~half the step budget, so completions (and therefore
        # mid-flight admissions) actually happen within a demo-sized run.
        # An EXPLICIT max_new is honored as asked — a request that outgrows
        # its cache stops at capacity and is counted, never silently clipped.
        if max_new is not None:
            cap = max(1, int(max_new))
        else:
            cap = max(1, min(max(2, steps // 2), s_max - prompt_len - 1))
        needs_tokens = "tokens" in model.prefill_batch_spec(batch, prompt_len,
                                                           s_max)
        queue = []
        for i in range(n_requests):
            plen = (int(rng.randint(max(1, prompt_len // 2), prompt_len + 1))
                    if vary_prompt else prompt_len)
            queue.append(
                {"id": i,
                 "prompt": rng.randint(2, cfg.vocab_size, size=(plen,)),
                 "prompt_len": plen if needs_tokens else 0,
                 # staggered lengths so completions (and admissions) interleave
                 "max_new": int(rng.randint(max(1, cap // 2), cap + 1))})

        def bucket_of(plen: int) -> int:
            b = 4
            while b < plen:
                b *= 2
            return min(b, s_max)

        # ---- caches + pager ---------------------------------------------
        if kv_layout == "paged":
            def req_pages(req):
                tokens_cap = min(req["prompt_len"] + req["max_new"], s_max)
                return pages_for(tokens_cap, page_size)

            pool_pages = o.get("pool_pages")
            if pool_pages is None:
                # hold the `batch` largest concurrent requests — strictly
                # below the contiguous batch*s_max worst case on mixed loads
                demand = sorted((req_pages(r) for r in queue), reverse=True)
                pool_pages = max(sum(demand[:batch]), 1)
            pool_pages = int(pool_pages)
            pager = SlotPager.build(batch, s_max, page_size, pool_pages)
            cache_kw = {"page_size": page_size, "pool_pages": pool_pages}
        else:
            pager = None
            cache_kw = {}
        caches = init_global_caches(model, mesh, axes, s_max=s_max,
                                    batch_global=batch,
                                    dtype=policy.kv_cache_dtype(), **cache_kw)
        kv_bytes = kv_cache_bytes(caches)
        kv_bytes_contig = kv_cache_bytes(jax.eval_shape(
            lambda: init_global_caches(model, mesh, axes, s_max=s_max,
                                       batch_global=batch,
                                       dtype=policy.kv_cache_dtype())))

        # ---- compiled steps ---------------------------------------------
        ptree = jax.eval_shape(lambda: qparams)
        ss = build_decode_step(model, mesh, axes, params_tree=ptree,
                               s_max=s_max, batch_global=batch, policy=policy,
                               attn_impl=attn_impl, **cache_kw)
        pf_cache: dict = {}

        def prefill_for(bucket: int):
            if bucket not in pf_cache:
                pf_cache[bucket] = build_cached_prefill(
                    model, mesh, axes, params_tree=ptree, s_max=s_max,
                    s_prompt=bucket, batch_global=batch, attn_impl=impl,
                    policy=policy, bos_id=BOS_ID, with_prompt_lens=True,
                    **cache_kw)
            return pf_cache[bucket]

        d_front = cfg.d_frontend or cfg.d_model
        n_img = cfg.n_image_tokens or 1601

        def prefill_batch(slots_to_fill, bucket: int):
            """Assemble the (B, ...) prefill inputs; only masked slots matter."""
            b = {}
            if needs_tokens:
                toks = np.ones((batch, bucket), np.int32)
                for s, req in slots_to_fill:
                    toks[s, : len(req["prompt"])] = req["prompt"]
                b["tokens"] = jnp.asarray(toks)
            if cfg.family == "vlm":
                key = jax.random.PRNGKey(seed + 101)
                b["images"] = jax.random.normal(key, (batch, n_img, d_front),
                                                jnp.float32)
            if cfg.family == "encdec":
                key = jax.random.PRNGKey(seed + 102)
                b["frames"] = jax.random.normal(key, (batch, s_max, d_front),
                                                jnp.float32)
            return b

        kv_bits = 16 if policy.kv_cache_dtype() == jnp.bfloat16 else 32
        kv_demotions = 0
        pool_pressure = 0.0

        # ---- slot state (host side) -------------------------------------
        active = np.zeros((batch,), bool)
        remaining = np.zeros((batch,), np.int64)
        slot_plen = np.zeros((batch,), np.int64)   # tokens cached at admit
        slot_cap = np.full((batch,), s_max, np.int64)
        seqs = [[] for _ in range(batch)]
        finished = []
        cur_tok = jnp.full((batch, 1), BOS_ID, jnp.int32)
        admitted = completed = decoded = 0
        capacity_stops = 0
        deferred_ids: set = set()   # requests that waited at least once

        def req_cap(req):
            return min(req["prompt_len"] + req["max_new"], s_max)

        def admit():
            nonlocal caches, cur_tok, admitted, pool_pressure
            free = [i for i in range(batch) if not active[i]]
            fill = []
            if pager is None:
                while free and queue:
                    fill.append((free.pop(0), queue.pop(0)))
            else:
                # FIFO with cascading reservation (plan_admissions): younger
                # requests may fill slots out of the page surplus, but every
                # freed page accrues to the oldest page-blocked request
                # first, so a big request is never starved by small ones
                demands = [pager.pages_for(req_cap(r)) for r in queue]
                take, blocked = plan_admissions(pager.pool.free_pages,
                                                len(free), demands)
                for qi in blocked:
                    if demands[qi] > pager.pool.n_pages:
                        raise ValueError(
                            f"page pool ({pager.pool.n_pages} pages) can "
                            f"never fit a {demands[qi]}-page request; raise "
                            "pool_pages")
                    # waited at least once for page reclaim (counted once
                    # per request, however many cycles it waits)
                    deferred_ids.add(queue[qi]["id"])
                for qi in take:
                    req = queue[qi]
                    slot = free.pop(0)
                    if not pager.admit(slot, req_cap(req)):
                        raise RuntimeError(
                            "admission plan out of sync with page pool")
                    fill.append((slot, req))
                for qi in sorted(take, reverse=True):
                    queue.pop(qi)
                # watermark signal: a page-blocked admission saturates the
                # pressure (the pool is effectively full for the queue even
                # if a few pages remain free)
                pool_pressure = 1.0 if blocked else pager.pool.pressure
            if not fill:
                return
            if pager is not None:
                caches = set_page_tables(caches, pager.table)
            new_tok = np.array(cur_tok)
            by_bucket: dict[int, list] = {}
            for s, req in fill:
                by_bucket.setdefault(bucket_of(len(req["prompt"])), []).append(
                    (s, req))
            for bucket, group in sorted(by_bucket.items()):
                pf = prefill_for(bucket)
                mask = np.zeros((batch,), bool)
                plens = np.ones((batch,), np.int32)
                for s, req in group:
                    mask[s] = True
                    plens[s] = len(req["prompt"])
                tok, caches_new = pf.fn(qparams, prefill_batch(group, bucket),
                                        caches, jnp.asarray(mask),
                                        jnp.asarray(plens))
                caches = caches_new
                tok = np.asarray(tok)
                for s, req in group:
                    active[s] = True
                    remaining[s] = req["max_new"]
                    slot_plen[s] = req["prompt_len"]
                    slot_cap[s] = (pager.slot_capacity(s) if pager is not None
                                   else s_max)
                    seqs[s] = [int(tok[s, 0])]
                    new_tok[s] = tok[s]
                    admitted += 1
            cur_tok = jnp.asarray(new_tok)

        def maybe_demote_kv():
            """f32 -> bf16 pool demotion when paged-KV pressure crosses the
            program's watermark (a one-way ratchet; the jitted decode step
            retraces once on the narrower cache dtype)."""
            nonlocal caches, kv_bits, kv_demotions
            if pager is None or kv_bits <= 16:
                return
            from repro.api.program import Observation

            obs = Observation(round=admitted, pool_pressure=pool_pressure)
            if self.program.kv_demote(obs):
                from repro.models.attention import demote_kv_cache

                caches = demote_kv_cache(caches, jnp.bfloat16)
                kv_bits = 16
                kv_demotions += 1
                say(f"kv cache: pool pressure {pool_pressure:.2f} >= "
                    f"watermark {self.program.kv_watermark} -> demoted "
                    "f32 pools to bf16")

        admit()
        maybe_demote_kv()
        # first call compiles; its output is a real decode step, consumed below
        tok, caches = ss.fn(qparams, {"token": cur_tok}, caches)
        tok_h = np.asarray(tok)               # sync: compile finishes here
        t0, step_i, decoded_at_t0 = time.time(), 1, 0
        while True:
            done_any = False
            for s in range(batch):
                if not active[s]:
                    continue
                seqs[s].append(int(tok_h[s, 0]))
                decoded += 1
                remaining[s] -= 1
                # tokens cached so far (the newest token is not written until
                # it is fed back)
                cached = slot_plen[s] + len(seqs[s]) - 1
                done = remaining[s] <= 0
                if not done and cached >= slot_cap[s]:
                    # cache full: STOP the slot — decoding on would drop K/V
                    # writes and silently degrade the context (the old
                    # driver's failure mode)
                    done = True
                    capacity_stops += 1
                if done:
                    active[s] = False
                    if pager is not None:
                        pager.evict(s)
                    finished.append(seqs[s])
                    completed += 1
                    done_any = True
            if step_i == 1:
                decoded_at_t0 = decoded       # step 1 ran pre-timer (compile)
            if step_i >= steps or (not active.any() and not queue):
                break
            if done_any and pager is not None:
                # cleared table rows make the evicted slots' future writes
                # drop instead of landing on reclaimed pages
                caches = set_page_tables(caches, pager.table)
            cur_tok = jnp.asarray(tok_h)      # each slot feeds its own last token
            if done_any and queue:
                admit()                       # mid-flight slot reuse: overwrites
                                              # the admitted slots in cur_tok
                maybe_demote_kv()
            tok, caches = ss.fn(qparams, {"token": cur_tok}, caches)
            tok_h = np.asarray(tok)
            step_i += 1
        wall = time.time() - t0

        stats = ServeStats(
            arch=self.spec.arch, bits=serve_bits, attn_impl=attn_impl,
            decode_steps=step_i, decoded_tokens=decoded, completed=completed,
            admitted=admitted, wall_s=wall,
            tok_s=(decoded - decoded_at_t0) / max(wall, 1e-9),
            bytes_per_step_packed=q_bytes, bytes_per_step_f32=f32_bytes,
            packed_vs_f32=q_bytes / max(f32_bytes, 1),
            sample=(finished[0] if finished else seqs[0])[:16],
            kv_layout=kv_layout,
            page_size=page_size if kv_layout == "paged" else 0,
            kv_bytes=kv_bytes, kv_bytes_contiguous=kv_bytes_contig,
            capacity_stops=capacity_stops,
            deferred_admissions=len(deferred_ids),
            prompt_buckets=sorted(pf_cache),
            kv_demotions=kv_demotions,
            kv_bits_final=kv_bits,
        )
        say(f"decoded {stats.decoded_tokens} tokens over {stats.decode_steps} "
            f"steps x {batch} slots in {wall:.3f}s = {stats.tok_s:.1f} tok/s "
            f"on {jax.devices()[0].platform}")
        say(f"admitted {stats.admitted} / completed {stats.completed} sequences "
            f"(continuous batching over {n_requests} requests; "
            f"{capacity_stops} capacity stops, "
            f"{len(deferred_ids)} deferred admissions)")
        say(f"weight stream: {q_bytes/1e6:.1f} MB/step packed vs "
            f"{f32_bytes/1e6:.1f} MB/step f32 -> ratio {stats.packed_vs_f32:.3f}")
        if kv_layout == "paged":
            say(f"kv cache: {kv_bytes/1e6:.2f} MB paged pool "
                f"(page={page_size}, buckets={stats.prompt_buckets}) vs "
                f"{kv_bytes_contig/1e6:.2f} MB contiguous")
        say(f"sample: {stats.sample}")
        return stats

    # ------------------------------------------------------------------
    # dryrun: AOT lower + compile + roofline
    # ------------------------------------------------------------------
    def trace(self, shape=None, variant: dict | None = None):
        """AOT-trace one (arch x shape) cell on this mesh — no compile.

        ``shape``: a shape-cell name from ``repro.configs.shapes_for`` or an
        explicit :class:`~repro.configs.base.ShapeSpec`.  Packed serving
        weights come from the session policy (``policy.packed``), not a knob.
        Returns ``(traced, meta)`` — ``traced.jaxpr`` feeds the static
        precision lint (:mod:`repro.analyze`), ``traced.lower()`` continues
        to the compile path :meth:`lower` wraps.
        """
        import dataclasses as _dc

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs import shapes_for
        from repro.configs.base import ShapeSpec
        from repro.dist.sharding import batch_specs
        from repro.launch.mesh import batch_size
        from repro.launch.steps import (
            build_decode_step, build_prefill_step, globalize,
            local_param_shapes, serving_axes)
        from repro.models.model import build_model
        from repro.optim import build_optimizer

        variant = dict(variant or self.spec.opt("variant") or {})
        spec = self.spec
        shape = shape if shape is not None else spec.opt("shape")
        cfg = self.cfg
        if variant.get("gather_bf16"):
            cfg = _dc.replace(cfg, fsdp_gather_dtype="bfloat16")
        if variant.get("capacity"):
            cfg = _dc.replace(cfg, capacity_factor=float(variant["capacity"]))
        if variant.get("no_remat"):
            cfg = _dc.replace(cfg, remat=False)
        model = build_model(cfg)
        if isinstance(shape, ShapeSpec):
            cell = shape
        else:
            cell = {s.name: s for s in shapes_for(cfg)}[shape]
        mesh, axes = self.mesh, self.axes
        rng_sds = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                       sharding=NamedSharding(mesh, P()))

        def _bf16(dt):
            return jnp.bfloat16 if jnp.issubdtype(dt, jnp.floating) else dt

        if cell.kind == "train":
            opt = build_optimizer("sgd", 1e-3)
            tc = self.train_config()
            from repro.launch.steps import build_train_step

            ts = build_train_step(model, mesh, axes, opt, tc, donate=False)
            pshapes = local_param_shapes(model, mesh, axes)
            params_g = globalize(pshapes, ts.param_specs, mesh)
            opt_shapes = jax.eval_shape(opt.init, pshapes)
            opt_g = globalize(opt_shapes, ts.opt_specs, mesh)
            batch_tree = model.train_batch_spec(cell.global_batch, cell.seq_len)
            bspecs = batch_specs(batch_tree, axes)
            batch_g = globalize(
                jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(
                        (l.shape[0] // batch_size(mesh, axes),) + l.shape[1:],
                        l.dtype),
                    batch_tree),
                bspecs, mesh)
            n_clients = ts.n_clients
            delta_g = jax.ShapeDtypeStruct(
                (n_clients,), jnp.float32,
                sharding=NamedSharding(mesh, P(
                    axes.batch_axes if len(axes.batch_axes) > 1
                    else axes.batch_axes[0])))
            step = ts.fn(batch_tree)
            traced = step.trace(params_g, opt_g, batch_g, delta_g, rng_sds)

        elif cell.kind == "prefill":
            wrap, pspecs = build_prefill_step(model, mesh, axes)
            pshapes = local_param_shapes(model, mesh, axes)
            params_g = globalize(pshapes, pspecs, mesh, dtype_map=_bf16)
            batch_tree = model.train_batch_spec(cell.global_batch, cell.seq_len)
            batch_tree = {k: v for k, v in batch_tree.items() if k != "labels"}
            bspecs = batch_specs(batch_tree, axes)
            batch_g = globalize(
                jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(
                        (l.shape[0] // batch_size(mesh, axes),) + l.shape[1:],
                        l.dtype),
                    batch_tree),
                bspecs, mesh)
            step = wrap(batch_tree)
            traced = step.trace(params_g, batch_g)

        else:  # decode
            sv_axes = serving_axes(axes, cell.global_batch, mesh)
            params_tree = None
            if self.policy.packed:
                # packed serving weights (QTensor): gathers stream codes
                from repro.models.common import pack_params_for_policy

                pshapes_local = local_param_shapes(model, mesh, sv_axes)
                params_tree = jax.eval_shape(
                    lambda: pack_params_for_policy(
                        jax.tree_util.tree_map(
                            lambda l: jnp.zeros(l.shape, l.dtype),
                            pshapes_local),
                        self.policy, jax.random.PRNGKey(0)))
            page_size = spec.opt("page_size")
            ss = build_decode_step(model, mesh, sv_axes, s_max=cell.seq_len,
                                   batch_global=cell.global_batch,
                                   params_tree=params_tree,
                                   policy=self.policy,
                                   page_size=(None if page_size is None
                                              else int(page_size)),
                                   pool_pages=spec.opt("pool_pages"),
                                   attn_impl=spec.opt("attn_impl", "ref"))
            params_g = globalize(ss.param_shapes, ss.param_specs, mesh,
                                 dtype_map=_bf16)
            caches_g = globalize(ss.caches_shape, ss.cache_specs, mesh)
            batch_tree = model.decode_batch_spec(cell.global_batch,
                                                 cell.seq_len)
            bspecs = batch_specs(batch_tree, sv_axes)
            bsz = batch_size(mesh, sv_axes)
            batch_g = globalize(
                jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(
                        (l.shape[0] // max(bsz, 1),) + l.shape[1:], l.dtype),
                    batch_tree),
                bspecs, mesh)
            traced = ss.fn.trace(params_g, batch_g, caches_g)

        n_dev = int(np.prod(mesh.devices.shape))
        meta = dict(arch=spec.arch, shape=cell.name, mesh=spec.mesh,
                    n_devices=n_dev, kind=cell.kind, seq_len=cell.seq_len,
                    global_batch=cell.global_batch)
        return traced, meta

    def lower(self, shape=None, variant: dict | None = None):
        """AOT-lower + compile one cell (the :meth:`trace` continuation).

        Returns ``(compiled, lowered, meta)``.
        """
        traced, meta = self.trace(shape, variant)
        lowered = traced.lower()
        return lowered.compile(), lowered, meta

    def analyze(self, *, compile: bool = True, allowlist: str | None = None,
                check_kernels: bool = True, rules=None,
                proofs: list | None = None) -> list:
        """Static precision / wire / kernel / range lint over this spec.

        Traces (and, with ``compile=True``, compiles) the step graphs the
        RunSpec implies and returns a list of
        :class:`repro.analyze.findings.Finding` — nothing is executed.
        ``allowlist`` names an ``analyze.toml`` to mark known-legitimate
        findings (``None`` skips allowlisting).  ``rules`` selects rule
        families (see ``repro.analyze.runner.ALL_RULE_FAMILIES``); the
        ``overflow``/``numerics`` families run the abstract interpreter and
        append positive proof records (accumulator headroom, error budget)
        to ``proofs`` when a list is passed.
        """
        from repro.analyze.runner import analyze_session

        return analyze_session(self, compile=compile,
                               allowlist_path=allowlist,
                               check_kernels=check_kernels,
                               rules=rules, proofs=proofs)

    def run_dryrun(self, shape=None, variant: dict | None = None,
                   *, verbose: bool = True) -> dict:
        """Lower+compile one cell and derive its roofline report dict."""
        from repro.configs import shapes_for
        from repro.configs.base import ShapeSpec
        from repro.roofline.analysis import analyze_compiled, model_flops

        t0 = time.time()
        shape = shape if shape is not None else self.spec.opt("shape")
        variant = dict(variant or self.spec.opt("variant") or {})
        compiled, lowered, meta = self.lower(shape, variant)
        if variant:
            meta["variant"] = dict(variant)
        cell = (shape if isinstance(shape, ShapeSpec)
                else {s.name: s for s in shapes_for(self.cfg)}[meta["shape"]])
        mf = model_flops(self.cfg, cell.kind, cell.seq_len, cell.global_batch)
        rep = analyze_compiled(compiled, arch=meta["arch"], shape=meta["shape"],
                               mesh_name=meta["mesh"],
                               n_devices=meta["n_devices"],
                               model_flops_global=mf)
        d = rep.to_dict()
        d.update(meta, compile_s=round(time.time() - t0, 1), status="ok")
        if verbose:
            print(f"[{meta['arch']} x {meta['shape']} x {meta['mesh']}] "
                  f"compile={d['compile_s']}s  "
                  f"compute={rep.compute_s:.3e}s memory={rep.memory_s:.3e}s "
                  f"collective={rep.collective_s:.3e}s  "
                  f"dominant={rep.dominant}  "
                  f"useful={rep.useful_flops_ratio:.3f}")
            print("  memory_analysis:", rep.memory_stats)
            print("  collectives:",
                  {k: v for k, v in rep.collective_breakdown.items()})
        return d

    # ------------------------------------------------------------------
    # fl-sim: the paper's CIFAR-class experiment loop
    # ------------------------------------------------------------------
    def run_fl_sim(self) -> dict:
        """FLSimulation (vmap Algorithm 1) + GBD orchestrator, CNN-scale."""
        import jax.numpy as jnp

        from repro.core.energy import heterogeneous_fleet, memory_capacities
        from repro.data import (ClientBatcher, SyntheticImages,
                                dirichlet_partition)
        from repro.fed.orchestrator import FLOrchestrator, OrchestratorConfig
        from repro.fed.simulation import FLSimulation, SimConfig
        from repro.models.cnn import mobilenet, resnet, xent_loss

        spec = self.spec
        o = spec.options
        n_clients = int(o.get("n_clients", 8))
        seed = spec.seed
        if spec.arch == "resnet":
            model = resnet(depth_blocks=(1, 1), width=8)
        elif spec.arch == "mobilenet":
            model = mobilenet(width=8, n_stages=2)
        else:
            raise ValueError(f"fl-sim arch must be one of {SIM_ARCHS}, "
                             f"got {spec.arch!r}")
        loss = xent_loss(model)
        sim = FLSimulation(loss, model.init,
                           SimConfig(n_clients=n_clients,
                                     lr=float(o.get("lr", 0.08)), seed=seed))
        imgs, labels = SyntheticImages(n=2048, hw=16, seed=seed).generate()
        parts = dirichlet_partition(labels, n_clients, alpha=0.5, seed=seed)
        batcher = ClientBatcher(imgs, labels, parts, batch=spec.batch,
                                seed=seed)
        fleet = heterogeneous_fleet(n_clients, seed=seed, group_step_mhz=5.0)
        caps = memory_capacities(n_clients, lo_mb=2.0, hi_mb=8.0) * 1e6
        orch = FLOrchestrator(
            OrchestratorConfig(
                n_devices=n_clients, n_rounds=spec.rounds,
                scheme=o.get("scheme", "fwq"),
                model_dim_d=int(o.get("model_dim_d", 1 << 16)),
                error_tolerance=float(o.get("error_tolerance", 4.5)),
                precision=self.policy, seed=seed,
                faults=o.get("faults"),
                program=o.get("precision_program"),
                resolve_drift_db=float(o.get("resolve_drift_db", 0.0)),
                ckpt_dir=str(o.get("ckpt_dir", "")),
                ckpt_every=int(o.get("ckpt_every", 10))),
            fleet, caps, grad_bytes=float(o.get("grad_bytes", 1e6)))

        def batch_fn(r, cohort):
            x, y = batcher.sample_round(r, cohort)
            return {"x": jnp.asarray(x), "y": jnp.asarray(y)}

        eval_every = int(o.get("eval_every", 0))
        eval_fn = None
        if eval_every:
            eimgs, elabels = SyntheticImages(n=512, hw=16,
                                             seed=seed + 999).generate()
            ebatch = {"x": jnp.asarray(eimgs), "y": jnp.asarray(elabels)}
            eval_fn = lambda s: s.evaluate(loss, ebatch)  # noqa: E731

        return orch.run(sim, batch_fn, eval_fn=eval_fn, eval_every=eval_every)
