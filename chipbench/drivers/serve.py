"""The serving driver: one ``Session.serve`` call is the window.

``Session.serve`` makes up its own request queue from ``RunSpec.seed``.
:func:`draw_requests` is the harness's copy of that draw (prompt length,
prompt ids, ``max_new``, in that order), used to count prompt tokens, to
know which prompt buckets the window will need, and to check that the
traffic did not move.

While the window runs, the harness wraps the step programs the call builds
(``build_cached_prefill`` and ``build_decode_step``, looked up by the call
at run time) in :class:`StepRecorder` proxies.  They keep each call's
inputs and outputs as device arrays and change no result; after the window
the harness replays them into each request's served tokens, which the
reference then scores.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


def draw_requests(seed: int, n: int, prompt_len: int, max_new: int,
                  vocab: int, vary_prompt: bool = True) -> list:
    """The queue ``Session.serve`` draws for an explicit ``max_new``."""
    rng = np.random.RandomState(seed)
    cap = max(1, int(max_new))
    out = []
    for i in range(n):
        plen = (int(rng.randint(max(1, prompt_len // 2), prompt_len + 1))
                if vary_prompt else prompt_len)
        prompt = rng.randint(2, vocab, size=(plen,))
        out.append({"id": i, "prompt": prompt,
                    "max_new": int(rng.randint(max(1, cap // 2), cap + 1))})
    return out


def bucket_of(plen: int, s_max: int) -> int:
    b = 4
    while b < plen:
        b *= 2
    return min(b, s_max)


@dataclasses.dataclass
class Stream:
    """One admitted request as the step programs served it."""

    slot: int
    prompt: np.ndarray
    max_new: int
    served: list
    done_at: tuple = ()     # (decode call, slot) of the last token
    reused: bool = False    # admitted into a slot a finished request freed

    @property
    def done(self) -> bool:
        return len(self.served) >= 1 + self.max_new


class StepRecorder:
    """Patches the step builders for the duration of a ``with`` block so
    that every step program the serve call builds records its calls."""

    def __init__(self):
        self.events: list = []

    @contextlib.contextmanager
    def installed(self):
        import jax

        from repro.launch import steps

        orig_pf, orig_dec = steps.build_cached_prefill, steps.build_decode_step
        events = self.events

        def wrap_prefill(*a, **kw):
            step = orig_pf(*a, **kw)
            fn = step.fn

            def call(params, batch, caches, mask, plens):
                with jax.profiler.TraceAnnotation("chipbench.prefill"):
                    tok, new = fn(params, batch, caches, mask, plens)
                events.append(("p", batch["tokens"], mask, plens, tok))
                return tok, new

            return dataclasses.replace(step, fn=call)

        def wrap_decode(*a, **kw):
            step = orig_dec(*a, **kw)
            fn = step.fn

            def call(params, batch, caches):
                with jax.profiler.TraceAnnotation("chipbench.decode"):
                    tok, new = fn(params, batch, caches)
                events.append(("d", batch["token"], tok))
                return tok, new

            return dataclasses.replace(step, fn=call)

        steps.build_cached_prefill = wrap_prefill
        steps.build_decode_step = wrap_decode
        try:
            yield self
        finally:
            steps.build_cached_prefill = orig_pf
            steps.build_decode_step = orig_dec

    def replay(self, queue: list) -> tuple:
        """Each admitted request's served tokens, in admission order, and
        per decode call the context lengths of the slots serving a request.
        Raises ``ValueError`` where the calls disagree with the queue."""
        by_prompt = {tuple(int(t) for t in r["prompt"]): r for r in queue}
        live: dict = {}
        used: set = set()
        streams, contexts, prefills = [], [], []
        for ev in self.events:
            if ev[0] == "p":
                toks, mask, plens, out = (np.asarray(a) for a in ev[1:])
                admitted = []
                for s in np.flatnonzero(mask):
                    prompt = toks[s, :int(plens[s])]
                    req = by_prompt.get(tuple(int(t) for t in prompt))
                    if req is None:
                        raise ValueError("a prefilled prompt is not in the "
                                         "harness's copy of the queue")
                    st = Stream(int(s), prompt, req["max_new"],
                                [int(out[s, 0])], reused=int(s) in used)
                    used.add(int(s))
                    live[int(s)] = st
                    streams.append(st)
                    admitted.append(len(prompt))
                prefills.append((toks.shape[1], admitted))
            else:
                fed, out = np.asarray(ev[1]), np.asarray(ev[2])
                ctx = []
                for s, st in list(live.items()):
                    if st.done:
                        del live[s]
                        continue
                    if int(fed[s, 0]) != st.served[-1]:
                        raise ValueError(f"slot {s} was fed {int(fed[s, 0])}, "
                                         f"not its last token {st.served[-1]}")
                    ctx.append(len(st.prompt) + len(st.served))
                    st.served.append(int(out[s, 0]))
                    if st.done:
                        st.done_at = (len(contexts), s)
                contexts.append(ctx)
        return streams, contexts, prefills


class ServeDriver:
    kind = "serve"

    def __init__(self, cfg: dict, wl: dict, mod, seed: int, seconds: float):
        self.cfg, self.wl, self.mod = cfg, wl, mod
        self.seed, self.seconds = int(seed), float(seconds)
        self._replayed = None

    def serve_kw(self) -> dict:
        wl = self.wl
        page = wl["page_size"]
        cap = min(wl["prompt_len"] + wl["max_new"], wl["s_max"])
        return dict(attn_impl="flash", kv_layout="paged", s_max=wl["s_max"],
                    page_size=page, pool_pages=wl["slots"] * -(-cap // page),
                    quiet=True)

    def window_steps(self) -> int:
        return max(1, round(self.seconds * self.wl["steps_per_s"]))

    def setup(self):
        import jax

        from repro.api import PrecisionPolicy, RunSpec, Session

        cfg, wl = self.cfg, self.wl
        t0 = time.perf_counter()
        self.sess = Session(RunSpec(
            arch=cfg["arch"], workload="serve", mesh="1x1",
            smoke=bool(cfg.get("smoke", False)), batch=wl["slots"],
            seq=wl["s_max"], seed=self.seed,
            precision=PrecisionPolicy.lazy_int8(cfg["weight_bits"])))
        self._check_config()
        jax.block_until_ready(self.sess.serving_params)
        t1 = time.perf_counter()
        self.queue = draw_requests(self.seed, wl["requests"], wl["prompt_len"],
                                   wl["max_new"], cfg["vocab_size"])
        self.buckets = sorted({bucket_of(len(r["prompt"]), wl["s_max"])
                               for r in self.queue})
        for b in self.buckets:
            # the window's programs, one prompt bucket at a time: a full
            # batch that finishes after one token, then one admission into
            # a freed slot (the eviction path)
            self.sess.serve(requests=wl["slots"] + 1, prompt_len=b,
                            vary_prompt=False, max_new=1, steps=3,
                            **self.serve_kw())
        self.steps = self.window_steps()
        self.phases = {"weights_s": t1 - t0,
                       "warm_buckets_s": time.perf_counter() - t1}

    def _check_config(self):
        c, cfg = self.sess.cfg, self.cfg
        ran = {"num_hidden_layers": c.n_layers, "hidden_size": c.d_model,
               "num_attention_heads": c.n_heads,
               "num_key_value_heads": c.n_kv_heads,
               "head_dim": c.resolved_head_dim,
               "intermediate_size": c.d_ff, "vocab_size": c.vocab_size,
               "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps}
        bad = {k: (v, cfg[k]) for k, v in ran.items() if v != cfg[k]}
        if bad:
            raise ValueError(f"the program's {c.name} differs from the "
                             f"configuration file: {bad}")

    def window(self) -> dict:
        import jax

        wl = self.wl
        self.rec = StepRecorder()
        with self.rec.installed():
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.serve"):
                self.stats = self.sess.serve(
                    requests=wl["requests"], prompt_len=wl["prompt_len"],
                    vary_prompt=True, max_new=wl["max_new"], steps=self.steps,
                    **self.serve_kw())
            self.wall_s = time.perf_counter() - t0
        st = self.stats
        self.attempted = st.admitted
        self.failed = st.capacity_stops
        self.notes = {"loop_s": st.wall_s, "steps": st.decode_steps,
                      "completed": st.completed,
                      "buckets": st.prompt_buckets}
        return {"serve_tok_s": st.decoded_tokens / self.wall_s}

    def replay(self):
        if self._replayed is None:
            streams, contexts, prefills = self.rec.replay(self.queue)
            self._replayed = (streams, contexts, prefills)
            self._traffic_checks(streams, prefills)
        return self._replayed

    def _traffic_checks(self, streams, prefills):
        st = self.stats
        finished = [s for s in streams if s.done]
        problems = []
        if len(streams) != st.admitted:
            problems.append(f"{len(streams)} streams replayed, "
                            f"{st.admitted} admitted")
        if len(finished) != st.completed:
            problems.append(f"{len(finished)} finished, {st.completed} "
                            "completed")
        decoded = sum(len(s.served) - 1 for s in streams)
        if decoded != st.decoded_tokens:
            problems.append(f"{decoded} tokens replayed, {st.decoded_tokens} "
                            "decoded")
        used = sorted({b for b, _ in prefills})
        if used != sorted(st.prompt_buckets):
            problems.append(f"buckets {used} replayed, {st.prompt_buckets} "
                            "reported")
        first = min(finished, key=lambda s: s.done_at, default=None)
        if first is not None and st.sample != first.served[:16]:
            problems.append("the first finished request's tokens differ from "
                            "the program's sample")
        if problems:
            raise ValueError("traffic moved: " + "; ".join(problems))

    def counters(self) -> dict:
        streams, contexts, prefills = self.replay()
        mod, cfg, wl = self.mod, self.cfg, self.wl
        prompt_ops = sum(mod.model_ops_prompt(cfg, n)
                         for _, adm in prefills for n in adm)
        token_ops = sum(mod.model_ops_token(cfg, c)
                        for ctx in contexts for c in ctx)
        return {
            "wall_s": self.wall_s,
            "loop_s": self.stats.wall_s,
            "model_ops": prompt_ops + token_ops,
            "quant_matmul_rows": ([wl["slots"] * b for b, _ in prefills]
                                  + [wl["slots"]] * len(contexts)),
            "flash_attention": [mod.flash_attention_cost(cfg, wl["slots"], b)
                                for b, _ in prefills],
            "flash_decode": [mod.flash_decode_cost(cfg, ctx, wl["page_size"])
                             for ctx in contexts],
        }

    def release(self):
        self.sess = None

    # -- correctness --------------------------------------------------------------
    def sample(self) -> list:
        """The requests the reference scores: the longest finished one, the
        request admitted into a freed slot that was served most tokens
        (finished or not: its served prefix), and others drawn from the
        seed among the finished."""
        streams, _, _ = self.replay()
        fin = [s for s in streams if s.done]
        if not fin:
            return []
        longest = max(fin, key=lambda s: len(s.served))
        reused = [s for s in streams if s.reused and s is not longest]
        pick = [longest]
        if reused:
            pick.append(max(reused, key=lambda s: len(s.served)))
        rest = [i for i, s in enumerate(fin)
                if all(s is not p for p in pick)]
        rng = np.random.RandomState(self.seed % (2 ** 32))
        k = min(self.wl["check_requests"] - len(pick), len(rest))
        drawn = sorted(rng.choice(rest, k, replace=False).tolist()
                       if k > 0 else [])
        return pick + [fin[i] for i in drawn]

    def reference(self, picked: list, **kw) -> list:
        seqs = [np.concatenate([s.prompt, s.served[:-1]]) for s in picked]
        pos = [np.arange(len(s.prompt) - 1, len(s.prompt) - 1 + len(s.served))
               for s in picked]
        wl = self.wl
        shape = (wl["check_requests"], wl["prompt_len"] + wl["max_new"])
        return self.mod.reference_logits(self.cfg, self.seed, seqs, pos,
                                         shape=shape, **kw)

    def check(self) -> list:
        picked = self.sample()
        if not picked:
            return [("unfinished", 1.0, 0.0)]
        self.picked = picked
        self.ref = logits = self.reference(picked)
        gaps = np.concatenate([self.mod.served_gaps(lg, s.served)
                               for lg, s in zip(logits, picked)])
        self.notes["scored"] = [(len(s.served), "freed slot" if s.reused
                                 else "first wave") for s in picked]
        return [("gap", float(gaps.max()), self.wl["limits"]["gap"])]


def build(cfg, wl, mod, seed, seconds):
    return ServeDriver(cfg, wl, mod, seed, seconds)
