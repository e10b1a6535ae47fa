"""Paged KV cache + batched flash-decode tests.

Pins the ISSUE-5 contracts: the paged reference decode is BITWISE-equal to
the contiguous cache (tp=1 and tp=4, both KV-sharded and sequence-parallel
layouts, ragged per-slot lengths, staggered admission reusing reclaimed
pages); the flash-decode Pallas kernel matches the gathered-softmax oracle;
a request that outruns its cache capacity terminates cleanly (counted, not
silently clipped); and over-long prompts raise instead of truncating.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.kernels import ops, ref
from repro.launch.mesh import axis_ctx_for, make_test_mesh
from repro.launch.paging import (
    PagePool, SlotPager, plan_admissions, set_page_tables)
from repro.launch.steps import (
    build_cached_prefill, build_decode_step, build_init_fn,
    init_global_caches)
from repro.models.attention import PagedKVCache
from repro.models.common import ParamCtx
from repro.models.model import build_model

MESH = make_test_mesh((1, 1), ("data", "model"))


def _contig_table(batch: int, n_pmax: int) -> np.ndarray:
    """Slot b owns pool rows [b*n_pmax, (b+1)*n_pmax) — capacity == s_max."""
    return np.arange(batch * n_pmax, dtype=np.int32).reshape(batch, n_pmax)


def _setup(arch="yi-6b", B=2, S_max=32, S_p=8, page=8):
    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg)
    axes = axis_ctx_for(MESH)
    init_fn, param_specs = build_init_fn(model, MESH, axes)
    params = init_fn(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(7), (B, S_p), 2,
                                cfg.vocab_size)
    return cfg, model, axes, params, param_specs, prompt


def _logit_fns(model, axes, param_specs, c_specs, *, with_plens=False,
               attn_impl="auto"):
    """shard_map'd (prefill, decode) returning LOCAL LOGITS, not tokens —
    the bitwise paged-vs-contiguous comparisons need the raw distribution
    (greedy argmax would mask softmax-normalization bugs)."""
    from jax.sharding import PartitionSpec as P

    def dec(p, tok, c):
        pc = ParamCtx(ctx=axes, compute_dtype=jnp.float32)
        return model.decode_step(pc, p, {"token": tok}, c,
                                 attn_impl=attn_impl)

    sm_dec = jax.jit(jax.shard_map(
        dec, mesh=MESH, in_specs=(param_specs, P(), c_specs),
        out_specs=(P(None, None, "model"), c_specs), check_vma=False))

    if with_plens:
        def pre(p, toks, c, plens):
            pc = ParamCtx(ctx=axes, compute_dtype=jnp.float32)
            return model.prefill(pc, p, {"tokens": toks}, c,
                                 prompt_lens=plens)

        sm_pre = jax.jit(jax.shard_map(
            pre, mesh=MESH, in_specs=(param_specs, P(), c_specs, P()),
            out_specs=(P(None, None, "model"), c_specs), check_vma=False))
    else:
        def pre(p, toks, c):
            pc = ParamCtx(ctx=axes, compute_dtype=jnp.float32)
            return model.prefill(pc, p, {"tokens": toks}, c)

        sm_pre = jax.jit(jax.shard_map(
            pre, mesh=MESH, in_specs=(param_specs, P(), c_specs),
            out_specs=(P(None, None, "model"), c_specs), check_vma=False))
    return sm_pre, sm_dec


def _paged_caches(model, B, S_max, page, **kw):
    caches = model.init_caches(B, S_max, tp=1, dtype=jnp.float32,
                               page_size=page, **kw)
    return set_page_tables(caches, _contig_table(B, S_max // page))


class TestPagedVsContiguous:
    def test_bitwise_logits_tp1(self):
        """Paged ref decode produces BITWISE-identical logits to the
        contiguous slab, at the model.decode_step level."""
        cfg, model, axes, params, pspecs, prompt = _setup()
        B, S_max, page = 2, 32, 8

        def run(paged: bool):
            from repro.dist.sharding import cache_specs
            if paged:
                caches = _paged_caches(model, B, S_max, page)
            else:
                caches = model.init_caches(B, S_max, tp=1, dtype=jnp.float32)
            pre, dec = _logit_fns(model, axes, pspecs,
                                  cache_specs(caches, axes, cfg))
            _, caches = pre(params, prompt, caches)
            outs = []
            tok = jnp.ones((B, 1), jnp.int32)
            for t in range(5):
                lg, caches = dec(params, tok + t, caches)
                outs.append(np.asarray(lg))
            return np.stack(outs)

        np.testing.assert_array_equal(run(False), run(True))

    def test_bitwise_logits_ragged_lengths(self):
        """Per-slot prompt lengths (bucketed right-padded prompts): paged and
        contiguous caches stamp/mask identically -> bitwise-equal logits."""
        cfg, model, axes, params, pspecs, prompt = _setup()
        B, S_max, page = 2, 32, 8
        plens = jnp.asarray([5, 8], jnp.int32)

        def run(paged: bool):
            from repro.dist.sharding import cache_specs
            if paged:
                caches = _paged_caches(model, B, S_max, page)
            else:
                caches = model.init_caches(B, S_max, tp=1, dtype=jnp.float32)
            pre, dec = _logit_fns(model, axes, pspecs,
                                  cache_specs(caches, axes, cfg),
                                  with_plens=True)
            lg, caches = pre(params, prompt, caches, plens)
            outs = [np.asarray(lg)]
            tok = jnp.ones((B, 1), jnp.int32)
            for t in range(4):
                lg, caches = dec(params, tok + t, caches)
                outs.append(np.asarray(lg))
            return np.stack(outs)

        np.testing.assert_array_equal(run(False), run(True))

    def test_ragged_prefill_matches_solo_short_prompt(self):
        """A right-padded slot decodes exactly what an unpadded prefill of
        its true prompt decodes (padding never enters cache or logits)."""
        cfg, model, axes, params, pspecs, _ = _setup()
        from repro.dist.sharding import cache_specs
        B, S_max, page = 2, 32, 8
        short = jax.random.randint(jax.random.PRNGKey(3), (B, 5), 2,
                                   cfg.vocab_size)

        def greedy(lg):
            return jnp.argmax(lg[:, -1, :], axis=-1).astype(jnp.int32)[:, None]

        def run(tokens, plens):
            caches = _paged_caches(model, B, S_max, page)
            cs = cache_specs(caches, axes, cfg)
            pre, dec = _logit_fns(model, axes, pspecs, cs,
                                  with_plens=plens is not None)
            args = (params, tokens, caches) + (
                (plens,) if plens is not None else ())
            lg, caches = pre(*args)
            tok = greedy(lg)
            toks = [np.asarray(tok)]
            for _ in range(4):
                lg, caches = dec(params, tok, caches)
                tok = greedy(lg)
                toks.append(np.asarray(tok))
            return np.stack(toks)

        padded = jnp.concatenate(
            [short, jnp.ones((B, 3), jnp.int32)], axis=1)   # pad to 8
        np.testing.assert_array_equal(
            run(short, None), run(padded, jnp.full((B,), 5, jnp.int32)))

    def test_staggered_admission_reuses_reclaimed_pages(self):
        """Evicting B and admitting C onto B's reclaimed pages must not
        disturb A (still decoding), and C must decode exactly its solo run."""
        cfg, model, axes, params, _pspecs, _ = _setup()
        B, S_max, S_p, page = 2, 32, 8, 8
        pa, pb, pc_prompt = (jax.random.randint(jax.random.PRNGKey(k), (S_p,),
                                                2, cfg.vocab_size)
                             for k in (21, 22, 23))
        n_pmax = S_max // page
        # pool holds exactly two live requests: C MUST reuse B's pages
        pager = SlotPager.build(B, S_max, page, pool_pages=2 * n_pmax)

        ss = build_decode_step(model, MESH, axes, s_max=S_max, batch_global=B,
                               page_size=page, pool_pages=2 * n_pmax)
        pf = build_cached_prefill(model, MESH, axes, s_max=S_max,
                                  s_prompt=S_p, batch_global=B,
                                  page_size=page, pool_pages=2 * n_pmax)

        def fresh():
            return init_global_caches(model, MESH, axes, s_max=S_max,
                                      batch_global=B, page_size=page,
                                      pool_pages=2 * n_pmax)

        def solo(prompt, n):
            sp = SlotPager.build(B, S_max, page, pool_pages=2 * n_pmax)
            sp.admit(0, S_max), sp.admit(1, S_max)
            caches = set_page_tables(fresh(), sp.table)
            toks = jnp.broadcast_to(prompt[None], (B, S_p))
            tok, caches = pf.fn(params, {"tokens": toks}, caches,
                                jnp.ones((B,), jnp.bool_))
            out = [int(np.asarray(tok)[0, 0])]
            for _ in range(n):
                tok, caches = ss.fn(params, {"token": tok}, caches)
                out.append(int(np.asarray(tok)[0, 0]))
            return out

        want_a, want_b, want_c = solo(pa, 7), solo(pb, 2), solo(pc_prompt, 3)

        pager.admit(0, S_max), pager.admit(1, S_max)
        caches = set_page_tables(fresh(), pager.table)
        tok, caches = pf.fn(params, {"tokens": jnp.stack([pa, pb])}, caches,
                            jnp.ones((B,), jnp.bool_))
        cur = np.array(tok)
        got_a, got_b = [int(cur[0, 0])], [int(cur[1, 0])]
        for _ in range(2):
            tok, caches = ss.fn(params, {"token": jnp.asarray(cur)}, caches)
            cur = np.array(tok)
            got_a.append(int(cur[0, 0]))
            got_b.append(int(cur[1, 0]))
        # B done: evict, then admit C onto the very pages B just freed
        freed = pager.evict(1)
        assert freed == n_pmax
        assert pager.admit(1, S_max)
        caches = set_page_tables(caches, pager.table)
        tok2, caches = pf.fn(params,
                             {"tokens": jnp.stack([pc_prompt, pc_prompt])},
                             caches, jnp.asarray([False, True]))
        cur[1] = np.asarray(tok2)[1]
        got_c = [int(cur[1, 0])]
        for _ in range(3):
            tok, caches = ss.fn(params, {"token": jnp.asarray(cur)}, caches)
            cur = np.array(tok)
            got_a.append(int(cur[0, 0]))
            got_c.append(int(cur[1, 0]))
        # A: 2 pre-eviction + 3 post-eviction decodes; all must match solo
        assert got_a == want_a[:6]
        assert got_b == want_b
        assert got_c == want_c

    @pytest.mark.parametrize("arch,layout", [
        ("yi-6b", "kv-sharded"),          # smoke n_kv=4, tp=4 -> kv heads split
        ("glm4-9b", "seq-parallel"),      # smoke n_kv=2, tp=4 -> seq sharded
    ])
    def test_tp4_bitwise_logits(self, arch, layout):
        """tp=4, both cache shardings: paged decode logits are bitwise-equal
        to the contiguous cache on the same mesh/params.

        Subprocess so XLA gets fake host devices before jax initializes."""
        script = _TP4_SCRIPT % {
            "src": os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "src"),
            "arch": arch, "layout": layout}
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "PAGED_TP4_OK" in out.stdout


_TP4_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, %(src)r)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config, smoke_variant
from repro.launch.mesh import axis_ctx_for, make_test_mesh
from repro.launch.paging import set_page_tables
from repro.launch.steps import (build_cached_prefill, build_decode_step,
                                build_init_fn, init_global_caches)
from repro.models.common import ParamCtx
from repro.models.model import build_model
from repro.models.attention import kv_cache_seq_parallel
from repro.models.transformer import attn_dims

TP, B, S_MAX, S_P, PAGE = 4, 2, 32, 6, 4
cfg = smoke_variant(get_config(%(arch)r))
ad = attn_dims(cfg, TP)
seqpar = kv_cache_seq_parallel(ad)
assert seqpar == (%(layout)r == "seq-parallel"), (seqpar, %(layout)r)
model = build_model(cfg)
mesh = make_test_mesh((1, TP), ("data", "model"))
axes = axis_ctx_for(mesh)
init_fn, param_specs = build_init_fn(model, mesh, axes)
params = init_fn(jax.random.PRNGKey(0))
params = jax.tree_util.tree_map(
    lambda x: jax.device_put(np.asarray(x), x.sharding), params)
prompt = jax.random.randint(jax.random.PRNGKey(5), (B, S_P), 2, cfg.vocab_size)

def decode_logits(paged):
    kw = {"page_size": PAGE} if paged else {}
    caches = init_global_caches(model, mesh, axes, s_max=S_MAX,
                                batch_global=B, **kw)
    if paged:
        if seqpar:
            # shard t owns positions [t*8, (t+1)*8) -> 2 local pages; slot b
            # gets local rows [2b, 2b+1] of every shard's private pool
            n_loc = (S_MAX // TP) // PAGE
            table = np.zeros((B, TP * n_loc), np.int32)
            for b in range(B):
                for t in range(TP):
                    table[b, t * n_loc:(t + 1) * n_loc] = np.arange(
                        b * n_loc, (b + 1) * n_loc)
        else:
            n_pmax = S_MAX // PAGE
            table = np.arange(B * n_pmax, dtype=np.int32).reshape(B, n_pmax)
        caches = set_page_tables(caches, table)
    pf = build_cached_prefill(model, mesh, axes, s_max=S_MAX, s_prompt=S_P,
                              batch_global=B, **kw)
    ss_specs = build_decode_step(model, mesh, axes, s_max=S_MAX,
                                 batch_global=B, **kw)

    def local(p, tok, c):
        pc = ParamCtx(ctx=axes, compute_dtype=jnp.float32)
        lg, nc = model.decode_step(pc, p, {"token": tok}, c)
        return lg, nc

    sm = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs, P(), ss_specs.cache_specs),
        out_specs=(P(None, None, "model"), ss_specs.cache_specs),
        check_vma=False))
    tok, caches = pf.fn(params, {"tokens": prompt}, caches,
                        jnp.ones((B,), jnp.bool_))
    outs = []
    for t in range(5):
        # fixed token stream so both layouts see identical inputs even if a
        # greedy tie ever flipped
        lg, caches = sm(params, jnp.full((B, 1), 2 + t, jnp.int32), caches)
        outs.append(np.asarray(lg))
    return np.stack(outs)

np.testing.assert_array_equal(decode_logits(False), decode_logits(True))
print("PAGED_TP4_OK")
"""


class TestPagedFamilies:
    @pytest.mark.parametrize("arch", ["jamba-1.5-large-398b",
                                      "llama-3.2-vision-90b",
                                      "seamless-m4t-large-v2"])
    def test_paged_matches_contiguous_greedy(self, arch):
        """Hybrid (paged attn sublayers + SSM states), VLM (paged self +
        contiguous cross slabs), enc-dec (paged decoder self): the paged
        cache emits the same greedy tokens as the contiguous reference."""
        cfg = smoke_variant(get_config(arch))
        model = build_model(cfg)
        axes = axis_ctx_for(MESH)
        init_fn, _ = build_init_fn(model, MESH, axes)
        params = init_fn(jax.random.PRNGKey(0))
        B, S_max, S_p, page = 2, 32, 8, 8
        spec = model.prefill_batch_spec(B, S_p, S_max)
        batch = {}
        for name, sds in spec.items():
            if sds.dtype == jnp.int32:
                batch[name] = jax.random.randint(jax.random.PRNGKey(11),
                                                 sds.shape, 2, cfg.vocab_size)
            else:
                batch[name] = jax.random.normal(jax.random.PRNGKey(12),
                                                sds.shape, dtype=sds.dtype)

        def run(paged: bool):
            kw = {"page_size": page} if paged else {}
            pf = build_cached_prefill(model, MESH, axes, s_max=S_max,
                                      s_prompt=S_p, batch_global=B, **kw)
            ss = build_decode_step(model, MESH, axes, s_max=S_max,
                                   batch_global=B, **kw)
            caches = init_global_caches(model, MESH, axes, s_max=S_max,
                                        batch_global=B, **kw)
            if paged:
                caches = set_page_tables(caches,
                                         _contig_table(B, S_max // page))
            tok, caches = pf.fn(params, batch, caches,
                                jnp.ones((B,), jnp.bool_))
            out = [np.asarray(tok)]
            for _ in range(4):
                tok, caches = ss.fn(params, {"token": tok}, caches)
                out.append(np.asarray(tok))
            return np.stack(out)

        np.testing.assert_array_equal(run(False), run(True))


class TestFlashDecodeKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_gathered_softmax(self, dtype):
        """Kernel output == gathered-contiguous softmax oracle, for both
        KV storage dtypes (PrecisionPolicy.kv_cache 32 and 16)."""
        rng = np.random.RandomState(1)
        B, KV, G, hd, page, n_pmax, N = 3, 2, 2, 16, 8, 4, 10
        q = jnp.asarray(rng.randn(B, KV, G, hd).astype(np.float32))
        kp = jnp.asarray(rng.randn(N, page, KV, hd).astype(np.float32))
        vp = jnp.asarray(rng.randn(N, page, KV, hd).astype(np.float32))
        pt = np.full((B, n_pmax), -1, np.int32)
        pt[0, :2] = [3, 7]
        pt[1, :4] = [0, 1, 2, 9]
        pt[2, :1] = [5]
        lens = np.array([13, 30, 4], np.int32)
        acc, m, l = ops.flash_paged_decode(q, kp.astype(dtype),
                                           vp.astype(dtype),
                                           jnp.asarray(pt), jnp.asarray(lens))
        got = np.asarray(acc / np.maximum(np.asarray(l), 1e-30))
        want = np.asarray(ref.flash_decode_ref(
            q, kp.astype(dtype), vp.astype(dtype), jnp.asarray(pt),
            jnp.asarray(lens)))
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    def test_flash_decode_logits_match_ref_paged(self):
        """Flash-decode LOGITS match the paged reference to fp32 tolerance
        (per-token greedy equality alone would hide a softmax-normalization
        bug — e.g. masking one extra unwritten position deflates every
        logit but rarely flips the argmax)."""
        cfg, model, axes, params, pspecs, prompt = _setup()
        from repro.dist.sharding import cache_specs
        B, S_max, page = 2, 32, 8

        def run(attn_impl):
            caches = _paged_caches(model, B, S_max, page)
            pre, dec = _logit_fns(model, axes, pspecs,
                                  cache_specs(caches, axes, cfg),
                                  attn_impl=attn_impl)
            _, caches = pre(params, prompt, caches)
            outs = []
            tok = jnp.ones((B, 1), jnp.int32)
            for t in range(5):
                lg, caches = dec(params, tok + t, caches)
                outs.append(np.asarray(lg))
            return np.stack(outs)

        np.testing.assert_allclose(run("flash"), run("ref"),
                                   rtol=2e-5, atol=2e-5)

    def test_flash_decode_greedy_matches_ref_paged(self):
        """End-to-end: flash-decode step emits the same greedy tokens as the
        paged reference (and therefore as the contiguous cache)."""
        cfg, model, axes, params, _pspecs, prompt = _setup()
        B, S_max, S_p, page = 2, 32, 8, 8
        table = _contig_table(B, S_max // page)

        def run(attn_impl):
            ss = build_decode_step(model, MESH, axes, s_max=S_max,
                                   batch_global=B, page_size=page,
                                   attn_impl=attn_impl)
            pf = build_cached_prefill(model, MESH, axes, s_max=S_max,
                                      s_prompt=S_p, batch_global=B,
                                      page_size=page)
            caches = set_page_tables(
                init_global_caches(model, MESH, axes, s_max=S_max,
                                   batch_global=B, page_size=page), table)
            tok, caches = pf.fn(params, {"tokens": prompt}, caches,
                                jnp.ones((B,), jnp.bool_))
            out = [np.asarray(tok)]
            for _ in range(5):
                tok, caches = ss.fn(params, {"token": tok}, caches)
                out.append(np.asarray(tok))
            return np.stack(out)

        np.testing.assert_array_equal(run("ref"), run("flash"))


class TestCapacityGuard:
    def test_capacity_exceeding_request_terminates_cleanly(self):
        """ISSUE-5 headline regression: max_new far past the cache capacity
        must stop AT capacity with exactly (s_max - prompt + 1) tokens per
        sequence, counted in capacity_stops — never silently clipped."""
        from repro.launch.serve import run_serve

        B, S_MAX, S_P = 2, 32, 8
        for layout in ("paged", "contiguous"):
            stats = run_serve("yi-6b", smoke=True, steps=64, batch=B,
                              s_max=S_MAX, prompt_len=S_P, serve_bits=7,
                              requests=B, max_new=100, kv_layout=layout,
                              quiet=True)
            assert stats.capacity_stops == B, (layout, stats)
            assert stats.completed == B
            # each slot: 1 prefill token + (s_max - prompt) decodes
            assert stats.decoded_tokens == B * (S_MAX - S_P), (layout, stats)
            assert stats.decode_steps == S_MAX - S_P

    def test_pool_exhaustion_defers_admission(self):
        """A pool too small for the whole queue defers admissions until
        reclaim — every request still completes."""
        from repro.launch.serve import run_serve

        stats = run_serve("yi-6b", smoke=True, steps=40, batch=4, s_max=64,
                          prompt_len=8, serve_bits=7, requests=6, max_new=6,
                          page_size=8, pool_pages=4, quiet=True)
        assert stats.deferred_admissions > 0
        assert stats.completed == 6
        assert stats.kv_bytes < stats.kv_bytes_contiguous

    def test_impossible_request_raises(self):
        pool = SlotPager.build(2, 32, 8, pool_pages=1)
        with pytest.raises(ValueError, match="can never fit"):
            pool.admit(0, 32)

    def test_page_pool_free_list(self):
        pool = PagePool(4)
        a = pool.alloc(3)
        assert pool.free_pages == 1
        assert pool.alloc(2) is None        # all-or-nothing
        pool.free(a)
        assert pool.free_pages == 4
        with pytest.raises(ValueError):
            pool.free([99])


class TestAdmissionFairness:
    def test_fifo_within_slot_limit(self):
        admit, blocked = plan_admissions(4, 2, [1, 2, 1])
        assert admit == [0, 1]
        assert blocked == []                # third hit the slot limit, not pages

    def test_blocked_head_reserves_everything(self):
        """An oversized head request reserves all free pages: younger small
        requests see zero surplus and must wait behind it."""
        admit, blocked = plan_admissions(3, 4, [4, 1, 1])
        assert admit == [] and blocked == [0, 1, 2]

    def test_no_leapfrogging_past_a_blocked_request(self):
        """A page-blocked request reserves every usable page, so younger
        requests cannot leapfrog it — strict FIFO on the page resource."""
        admit, blocked = plan_admissions(5, 4, [4, 1, 6, 1])
        assert admit == [0, 1]             # fits before anything blocks
        assert blocked == [2, 3]           # and nothing passes index 2

    def test_big_request_admits_under_sustained_small_load(self):
        """Starvation regression: with one page reclaimed per cycle and a
        fresh small request arriving every cycle, the big head-of-queue
        request must still admit (freed pages accrue to it via reservation;
        a grab-what-fits policy would hand every page to the newcomers)."""
        queue = [5]                        # big request waiting, pool drained
        free = 0
        admitted = []
        for _ in range(20):
            free += 1                      # one completion reclaims a page
            queue.append(1)                # sustained small-request load
            take, _blocked = plan_admissions(free, 8, queue)
            for qi in reversed(take):
                need = queue.pop(qi)
                free -= need
                admitted.append(need)
            if 5 in admitted:
                break
        assert 5 in admitted
        # and it got there in exactly the 5 cycles its demand requires
        assert len([a for a in admitted if a == 1]) == 0

    def test_serve_rejects_request_that_can_never_fit(self):
        """A request whose page demand exceeds the whole pool must raise at
        admission planning (waiting would deadlock the queue forever)."""
        from repro.launch.serve import run_serve

        with pytest.raises(ValueError, match="can never fit"):
            run_serve("yi-6b", smoke=True, steps=8, batch=2, s_max=64,
                      prompt_len=8, serve_bits=7, requests=2, max_new=40,
                      page_size=8, pool_pages=2, quiet=True)

    def test_mixed_load_completes_with_tight_pool(self):
        """Ragged prompts + staggered caps against a pool sized for barely
        more than the largest single request: every request completes, with
        deferrals along the way."""
        from repro.launch.serve import run_serve

        stats = run_serve("yi-6b", smoke=True, steps=64, batch=4, s_max=64,
                          prompt_len=8, serve_bits=7, requests=8, max_new=12,
                          page_size=8, pool_pages=4, vary_prompt=True,
                          quiet=True)
        assert stats.completed == 8
        assert stats.deferred_admissions > 0


class TestPrefillBounds:
    def test_prompt_at_exact_capacity_works(self):
        """S_p == s_max boundary: prefill fills every position and decode
        still runs (its K/V write drops; attention sees the full window)."""
        cfg, model, axes, params, _pspecs, _ = _setup()
        B = 2
        S = 16
        prompt = jax.random.randint(jax.random.PRNGKey(9), (B, S), 2,
                                    cfg.vocab_size)
        for kw in ({}, {"page_size": 8}):
            pf = build_cached_prefill(model, MESH, axes, s_max=S, s_prompt=S,
                                      batch_global=B, **kw)
            ss = build_decode_step(model, MESH, axes, s_max=S, batch_global=B,
                                   **kw)
            caches = init_global_caches(model, MESH, axes, s_max=S,
                                        batch_global=B, **kw)
            if kw:
                caches = set_page_tables(caches, _contig_table(B, S // 8))
            tok, caches = pf.fn(params, {"tokens": prompt}, caches,
                                jnp.ones((B,), jnp.bool_))
            assert np.all(np.isfinite(np.asarray(tok)))
            tok, caches = ss.fn(params, {"token": tok}, caches)
            assert np.all(np.isfinite(np.asarray(tok)))

    def test_prompt_past_capacity_raises(self):
        """S_p > s_max must raise (the old path silently jnp.clip-truncated
        the prompt), for both cache layouts."""
        cfg, model, axes, params, _pspecs, _ = _setup()
        B, S = 2, 16
        prompt = jax.random.randint(jax.random.PRNGKey(9), (B, S + 1), 2,
                                    cfg.vocab_size)
        for kw in ({}, {"page_size": 8}):
            caches = init_global_caches(model, MESH, axes, s_max=S,
                                        batch_global=B, **kw)
            if kw:
                caches = set_page_tables(caches, _contig_table(B, S // 8))
            pf = build_cached_prefill(model, MESH, axes, s_max=S,
                                      s_prompt=S + 1, batch_global=B, **kw)
            with pytest.raises(ValueError, match="exceeds the KV-cache"):
                pf.fn(params, {"tokens": prompt}, caches,
                      jnp.ones((B,), jnp.bool_))
