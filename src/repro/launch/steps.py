"""Step builders: FWQ train step + quantized serve step under one shard_map.

``build_train_step`` realizes Algorithm 1 on the pod (see DESIGN.md §4):
each data-parallel group *is* one FL client; the per-client bit-width enters
as a traced resolution scalar ``delta[i]`` so one compiled program serves any
heterogeneous assignment the GBD layer emits between rounds.

``build_decode_step`` / ``build_prefill`` realize the serving path with
(optionally) packed int8 weights.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.fwq import make_inline_quantizer
from repro.dist.collectives import AxisCtx, quantized_psum_batch
from repro.dist.sharding import batch_specs, cache_specs, tree_param_specs
from repro.launch.mesh import batch_size, fsdp_size, mesh_axis_size
from repro.models.common import ParamCtx, apply_fsdp_sharding, reduce_gradients
from repro.models.model import Model
from repro.optim import Optimizer

# Historical aliases (pre-facade importers).
_size = mesh_axis_size
_fsdp_size = fsdp_size
_batch_size = batch_size


def _compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32


def build_init_fn(model: Model, mesh, axes: AxisCtx):
    """Returns jit(shard_map) init: key -> sharded global param tree."""
    cfg = model.cfg
    tp = _size(mesh, axes.model_axis)
    fsdp = _fsdp_size(mesh, axes)

    def local_init(key):
        tp_idx = axes.tp_index()
        local_key = jax.random.fold_in(key, tp_idx)
        params = model.init(local_key, tp)
        pc = ParamCtx(ctx=axes, compute_dtype=_compute_dtype(cfg))
        return apply_fsdp_sharding(params, pc)

    # discover the local param structure without allocating
    shapes = jax.eval_shape(local_init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = tree_param_specs(shapes, cfg, axes, fsdp)
    sm = jax.shard_map(local_init, mesh=mesh, in_specs=P(),
                       out_specs=specs, check_vma=False)
    return jax.jit(sm), specs


@dataclasses.dataclass(frozen=True)
class TrainStep:
    fn: Any                     # jitted (params, opt_state, batch, delta, rng)
    param_specs: Any
    opt_specs: Any
    batch_spec_fn: Any          # (global_batch, seq) -> ShapeDtypeStruct tree
    n_clients: int


def build_train_step(model: Model, mesh, axes: AxisCtx, opt: Optimizer,
                     train_cfg: TrainConfig, *, attn_impl: str = "auto",
                     donate: bool = True) -> TrainStep:
    cfg = model.cfg
    fsdp = _fsdp_size(mesh, axes)
    n_clients = 1
    for a in axes.batch_axes:
        n_clients *= _size(mesh, a)

    def local_step(params, opt_state, batch, delta, rng):
        # ---- client identity & SR noise (deterministic, restartable) ----
        dp_idx = axes.dp_index()
        ckey = jax.random.fold_in(rng, dp_idx)
        delta_i = delta.reshape(())          # local (1,) -> scalar
        transform = make_inline_quantizer(delta_i, ckey)
        pc = ParamCtx(ctx=axes, transform=transform,
                      compute_dtype=_compute_dtype(cfg),
                      sp=cfg.seq_parallel,
                      gather_dtype=(jnp.bfloat16 if cfg.fsdp_gather_dtype == "bfloat16"
                                    else None))

        # ---- Algorithm 1 line 6: gradient AT the quantized weights -------
        def loss_fn(p):
            loss, aux = model.train_loss(pc, p, batch, attn_impl=attn_impl)
            return loss, aux

        (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)

        # ---- server aggregation (line 10), full precision -----------------
        if train_cfg.grad_compression_bits:
            # Beyond-paper: SR-quantized gradient all-reduce.  Applies ONLY to
            # replicated leaves — FSDP leaves are already reduce-scattered by
            # the all-gather transpose (compressing them again would both
            # double-reduce and move MORE bytes: the codes need an int32
            # accumulator on the wire).  See EXPERIMENTS.md §Perf (refuted
            # hypothesis H1.3) for the wire-model accounting.
            from repro.models.common import fsdp_plan
            paths_key = jax.random.fold_in(rng, 17)
            _, leaves, treedef, plan = fsdp_plan(
                params, axes.fsdp, check_divisibility=False)
            gleaves = jax.tree_util.tree_leaves(grads)
            out = []
            for i, (g, dim) in enumerate(zip(gleaves, plan)):
                if dim is not None:
                    out.append(g / axes.dp)          # already RS-summed
                else:
                    out.append(quantized_psum_batch(
                        axes, g, jax.random.fold_in(paths_key, i),
                        train_cfg.grad_compression_bits,
                        on_nonfinite=train_cfg.nonfinite_grads))
            grads = jax.tree_util.tree_unflatten(treedef, out)
        else:
            grads = reduce_gradients(grads, params, axes)

        # ---- server update (line 11) --------------------------------------
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), params, updates)

        # Diagnostic: sum over all shards of local grad sq norms (exact for
        # FSDP leaves, axis-multiplied for replicated ones — trend metric).
        gnorm = sum(jnp.vdot(g, g).real for g in jax.tree_util.tree_leaves(grads))
        all_axes = tuple(axes.batch_axes) + ((axes.model_axis,) if axes.model_axis else ())
        if all_axes:
            gnorm = jax.lax.psum(gnorm, all_axes)
        metrics = {
            "loss": jax.lax.pmean(loss, axes.batch_axes) if axes.batch_axes else loss,
            "grad_sq_shard_sum": gnorm,
        }
        return params, opt_state, metrics

    # ---- specs ---------------------------------------------------------
    pshapes = jax.eval_shape(
        lambda key: apply_fsdp_sharding(
            model.init(key, _size(mesh, axes.model_axis)),
            ParamCtx(ctx=axes, compute_dtype=_compute_dtype(cfg)), fsdp=fsdp),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    param_specs = tree_param_specs(pshapes, cfg, axes, fsdp)
    opt_shapes = jax.eval_shape(opt.init, pshapes)
    opt_specs = jax.tree_util.tree_map(
        lambda leaf: P(*([None] * len(leaf.shape))), opt_shapes)
    # momentum/adam states mirror param sharding
    opt_specs = _mirror_opt_specs(opt_shapes, pshapes, param_specs)

    def wrap(batch_tree_spec):
        bspecs = batch_specs(batch_tree_spec, axes)
        delta_spec = P(axes.batch_axes if len(axes.batch_axes) > 1
                       else (axes.batch_axes[0] if axes.batch_axes else None))
        sm = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(param_specs, opt_specs, bspecs, delta_spec, P()),
            out_specs=(param_specs, opt_specs,
                       {"loss": P(), "grad_sq_shard_sum": P()}),
            check_vma=False)
        donate_args = (0, 1) if donate else ()
        return jax.jit(sm, donate_argnums=donate_args)

    return TrainStep(fn=wrap, param_specs=param_specs, opt_specs=opt_specs,
                     batch_spec_fn=model.train_batch_spec, n_clients=n_clients)


def _mirror_opt_specs(opt_shapes, pshapes, param_specs):
    """Optimizer slots shaped like params inherit the param spec; scalars P()."""
    flat_p, _ = jax.tree_util.tree_flatten(pshapes)
    flat_s, _ = jax.tree_util.tree_flatten(param_specs)
    shape_to_spec = {}
    for leaf, spec in zip(flat_p, flat_s):
        shape_to_spec.setdefault((tuple(leaf.shape), str(leaf.dtype)), spec)

    def pick(leaf):
        key = (tuple(leaf.shape), str(leaf.dtype))
        key32 = (tuple(leaf.shape), "float32")
        if key in shape_to_spec:
            return shape_to_spec[key]
        if key32 in shape_to_spec:
            return shape_to_spec[key32]
        # match by shape only (f32 master copies of bf16 params)
        for (shp, _dt), spec in shape_to_spec.items():
            if shp == tuple(leaf.shape):
                return spec
        return P(*([None] * len(leaf.shape)))

    return jax.tree_util.tree_map(pick, opt_shapes)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def local_param_shapes(model: Model, mesh, axes: AxisCtx):
    """Per-shard parameter ShapeDtypeStructs (post-FSDP storage layout)."""
    cfg = model.cfg
    fsdp = _fsdp_size(mesh, axes)
    return jax.eval_shape(
        lambda key: apply_fsdp_sharding(
            model.init(key, _size(mesh, axes.model_axis)),
            ParamCtx(ctx=axes, compute_dtype=_compute_dtype(cfg)), fsdp=fsdp),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


@dataclasses.dataclass(frozen=True)
class ServeStep:
    fn: Any
    param_specs: Any
    cache_specs: Any
    # the number of input signatures the program has traced and loaded (its
    # jit cache's size); it stays with the step where a caller wraps ``fn``
    loads: Callable[[], int]
    param_shapes: Any = None
    caches_shape: Any = None


def _greedy_pick(axes: AxisCtx, tp: int, vl: int, logits):
    """Greedy token over vocab-parallel local logits (B, 1, V/tp) -> (B, 1)."""
    lg = logits[:, -1, :].astype(jnp.float32)
    mloc = jnp.max(lg, axis=-1)
    iloc = jnp.argmax(lg, axis=-1).astype(jnp.int32) + axes.tp_index() * vl
    if axes.model_axis and tp > 1:
        mglob = jax.lax.pmax(mloc, axes.model_axis)
        cand = jnp.where(mloc >= mglob, iloc, jnp.int32(2**30))
        nxt = jax.lax.pmin(cand, axes.model_axis)
    else:
        nxt = iloc
    return nxt[:, None]


def _cache_kwargs(page_size, pool_pages) -> dict:
    """init_caches kwargs for the requested KV layout (paged iff page_size)."""
    if page_size is None:
        return {}
    return {"page_size": int(page_size),
            "pool_pages": None if pool_pages is None else int(pool_pages)}


def build_decode_step(model: Model, mesh, axes: AxisCtx, *,
                      params_tree=None, s_max: int, batch_global: int,
                      policy=None,
                      page_size: int | None = None,
                      pool_pages: int | None = None, attn_impl: str = "ref"):
    """One-token decode step (greedy sampling over vocab-parallel logits).

    ``policy`` (:class:`repro.api.precision.PrecisionPolicy`): with
    ``policy.lazy``, packed ``QTensor`` weights stay int8 through the matmuls
    (quant_matmul kernel dispatch) instead of being dequantized on use.

    ``page_size`` switches the KV caches to the PAGED layout (shared
    per-shard pool of ``pool_pages`` pages + per-slot page tables —
    :class:`~repro.models.attention.PagedKVCache`); ``attn_impl="flash"``
    then routes decode attention through the batched flash-decode Pallas
    kernel instead of the (bitwise slab-equivalent) gather reference.
    """
    cfg = model.cfg
    tp = _size(mesh, axes.model_axis)
    fsdp = _fsdp_size(mesh, axes)
    from repro.models.transformer import padded_vocab_local
    vl = padded_vocab_local(cfg, tp)

    def serve_decode(params, batch, caches):
        pc = ParamCtx.from_policy(axes, policy,
                                  compute_dtype=_compute_dtype(cfg))
        logits, new_caches = model.decode_step(pc, params, batch, caches,
                                               attn_impl=attn_impl)
        return _greedy_pick(axes, tp, vl, logits), new_caches

    if params_tree is None:
        params_tree = jax.eval_shape(
            lambda key: apply_fsdp_sharding(
                model.init(key, tp), ParamCtx(ctx=axes), fsdp=fsdp),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    param_specs = tree_param_specs(params_tree, cfg, axes, fsdp)
    b_local = batch_global // max(_batch_size(mesh, axes), 1)
    caches_shape = jax.eval_shape(
        functools.partial(model.init_caches, b_local, s_max, tp,
                          **_cache_kwargs(page_size, pool_pages)))
    c_specs = cache_specs(caches_shape, axes, cfg)
    bspec_tree = model.decode_batch_spec(batch_global, s_max)
    bspecs = batch_specs(bspec_tree, axes)
    sm = jax.shard_map(serve_decode, mesh=mesh,
                       in_specs=(param_specs, bspecs, c_specs),
                       out_specs=(batch_specs(
                           {"token": jax.ShapeDtypeStruct((batch_global, 1), jnp.int32)},
                           axes)["token"], c_specs),
                       check_vma=False)
    fn = jax.jit(sm)
    return ServeStep(fn=fn, param_specs=param_specs, cache_specs=c_specs,
                     loads=fn._cache_size, param_shapes=params_tree,
                     caches_shape=caches_shape)


def init_global_caches(model: Model, mesh, axes: AxisCtx, *, s_max: int,
                       batch_global: int, dtype=jnp.float32,
                       page_size: int | None = None,
                       pool_pages: int | None = None):
    """Allocate the GLOBAL decode caches for a launch.

    ``model.init_caches`` returns per-shard LOCAL shapes (what the mapped
    function sees); the global arrays a jitted shard_map step consumes
    multiply every sharded dim by its axis size — e.g. the sequence-parallel
    KV cache stores S_max/tp per shard but S_max globally.  Passing the
    local-shaped tree as the global array silently truncates the cache on
    tp > 1 launches; always go through this helper (or ``globalize``).

    ``page_size``/``pool_pages`` select the paged KV layout; its page tables
    start all-unallocated (-1), everything else zeroed.
    """
    from repro.models.attention import PagedKVCache

    tp = _size(mesh, axes.model_axis)
    b_local = batch_global // max(_batch_size(mesh, axes), 1)
    shapes = jax.eval_shape(
        functools.partial(model.init_caches, b_local, s_max, tp, dtype=dtype,
                          **_cache_kwargs(page_size, pool_pages)))
    specs = cache_specs(shapes, axes, model.cfg)
    g = globalize(shapes, specs, mesh)

    def alloc(c):
        if isinstance(c, PagedKVCache):
            return PagedKVCache(
                jnp.zeros(c.k_pages.shape, c.k_pages.dtype),
                jnp.zeros(c.v_pages.shape, c.v_pages.dtype),
                jnp.full(c.page_table.shape, -1, c.page_table.dtype),
                jnp.zeros(c.length.shape, c.length.dtype))
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), c,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    return jax.tree_util.tree_map(
        alloc, g, is_leaf=lambda x: isinstance(x, PagedKVCache))


def build_cached_prefill(model: Model, mesh, axes: AxisCtx, *,
                         params_tree=None, s_max: int, s_prompt: int,
                         batch_global: int, attn_impl: str = "auto",
                         policy=None,
                         bos_id: int = 1, page_size: int | None = None,
                         pool_pages: int | None = None,
                         with_prompt_lens: bool = False,
                         with_logits: bool = False):
    """Prefill-into-slots step for continuous batching.

    The jitted fn signature is ``(params, batch, caches, slot_mask) ->
    (first_token (B, 1), merged_caches)``: it runs the model's real prefill
    (parallel forward with K/V capture for attention families, recurrence
    scan for SSM, encoder + cross-K/V fill for enc-dec/VLM) over a fresh
    zeroed cache, then merges ONLY the slots selected by ``slot_mask`` into
    the live caches — so new requests join a mid-flight batch without
    disturbing the sequences still decoding in the other slots.  Paged
    caches merge at page granularity through the live page tables, which the
    driver must have populated for the admitted slots BEFORE this call.

    ``attn_impl="flash"`` routes the prompt self-attention through the
    Pallas flash-attention kernel.  ``with_prompt_lens=True`` appends a
    ``prompt_lens (B,)`` argument — prompts right-padded to the ``s_prompt``
    bucket keep their true per-slot lengths (cache stamps, last-position
    logits), which is what makes one compiled prefill serve a whole bucket.
    ``with_logits=True`` also returns each slot's last-position logits
    ``(B, 1, V)`` between the token and the caches, for checking one
    implementation of the prefill against another.
    """
    cfg = model.cfg
    tp = _size(mesh, axes.model_axis)
    fsdp = _fsdp_size(mesh, axes)
    from repro.models.attention import fresh_slot_caches, merge_slot_caches
    from repro.models.transformer import padded_vocab_local
    vl = padded_vocab_local(cfg, tp)
    b_local = batch_global // max(_batch_size(mesh, axes), 1)

    def serve_prefill(params, batch, caches, slot_mask, prompt_lens=None):
        pc = ParamCtx.from_policy(axes, policy,
                                  compute_dtype=_compute_dtype(cfg))
        kw = {"prompt_lens": prompt_lens} if prompt_lens is not None else {}
        logits, filled = model.prefill(pc, params, batch,
                                       fresh_slot_caches(caches),
                                       attn_impl=attn_impl, **kw)
        if logits is None:      # enc-dec: decode starts from BOS
            tok = jnp.full((b_local, 1), bos_id, jnp.int32)
        else:
            tok = _greedy_pick(axes, tp, vl, logits)
        merged = merge_slot_caches(caches, filled, slot_mask)
        if with_logits:
            return tok, logits.astype(jnp.float32), merged
        return tok, merged

    if params_tree is None:
        params_tree = jax.eval_shape(
            lambda key: apply_fsdp_sharding(
                model.init(key, tp), ParamCtx(ctx=axes), fsdp=fsdp),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    param_specs = tree_param_specs(params_tree, cfg, axes, fsdp)
    caches_shape = jax.eval_shape(
        functools.partial(model.init_caches, b_local, s_max, tp,
                          **_cache_kwargs(page_size, pool_pages)))
    c_specs = cache_specs(caches_shape, axes, cfg)
    bspec_tree = model.prefill_batch_spec(batch_global, s_prompt, s_max)
    bspecs = batch_specs(bspec_tree, axes)
    mask_spec = batch_specs(
        {"m": jax.ShapeDtypeStruct((batch_global,), jnp.bool_)}, axes)["m"]
    tok_spec = batch_specs(
        {"token": jax.ShapeDtypeStruct((batch_global, 1), jnp.int32)},
        axes)["token"]
    in_specs = [param_specs, bspecs, c_specs, mask_spec]
    if with_prompt_lens:
        in_specs.append(mask_spec)          # (B,) int32, same batch sharding
    out_specs = (tok_spec, c_specs)
    if with_logits:                         # vocab-parallel local logits
        out_specs = (tok_spec, P(tok_spec[0], None, axes.model_axis),
                     c_specs)
    sm = jax.shard_map(serve_prefill, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    fn = jax.jit(sm)
    return ServeStep(fn=fn, param_specs=param_specs, cache_specs=c_specs,
                     loads=fn._cache_size, param_shapes=params_tree,
                     caches_shape=caches_shape)


def serving_axes(axes: AxisCtx, global_batch: int, mesh) -> AxisCtx:
    """Serving AxisCtx: when the request batch cannot shard over the batch
    axes (e.g. long_500k has batch 1), replicate the batch and keep FSDP."""
    if global_batch % max(_batch_size(mesh, axes), 1) == 0:
        return axes
    return AxisCtx(batch_axes=(), model_axis=axes.model_axis,
                   fsdp_axes=axes.fsdp_axes)


def build_prefill_step(model: Model, mesh, axes: AxisCtx, *, attn_impl="auto"):
    """Forward-only prefill: batch -> last-position local logits."""
    cfg = model.cfg
    fsdp = _fsdp_size(mesh, axes)

    def local_prefill(params, batch):
        pc = ParamCtx(ctx=axes, transform=None, compute_dtype=_compute_dtype(cfg),
                      sp=cfg.seq_parallel)
        loss_free = dict(batch)
        loss_free.pop("labels", None)
        logits = model.forward(pc, params, loss_free, attn_impl=attn_impl)
        return logits[:, -1:, :]

    pshapes = jax.eval_shape(
        lambda key: apply_fsdp_sharding(
            model.init(key, _size(mesh, axes.model_axis)),
            ParamCtx(ctx=axes), fsdp=fsdp),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    param_specs = tree_param_specs(pshapes, cfg, axes, fsdp)

    def wrap(batch_tree_spec):
        batch_no_labels = {k: v for k, v in batch_tree_spec.items() if k != "labels"}
        bspecs = batch_specs(batch_no_labels, axes)
        lead = (axes.batch_axes if len(axes.batch_axes) > 1
                else (axes.batch_axes[0] if axes.batch_axes else None))
        out_spec = P(lead, None, axes.model_axis)
        sm = jax.shard_map(local_prefill, mesh=mesh,
                           in_specs=(param_specs, bspecs),
                           out_specs=out_spec, check_vma=False)
        return jax.jit(sm)

    return wrap, param_specs


def globalize(sds_tree, spec_tree, mesh, *, dtype_map=None):
    """Local ShapeDtypeStructs + PartitionSpecs -> global SDS with shardings."""
    from jax.sharding import NamedSharding

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(sds, spec):
        shape = list(sds.shape)
        for d, entry in enumerate(tuple(spec)):
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                shape[d] *= sizes.get(a, 1)
        dt = sds.dtype
        if dtype_map:
            dt = dtype_map(dt)
        return jax.ShapeDtypeStruct(tuple(shape), dt,
                                    sharding=NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(
        one, sds_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
