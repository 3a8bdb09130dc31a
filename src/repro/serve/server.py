"""Continuous-batching decode server over live seed-reconstructed weights.

One :class:`DecodeServer` owns a full parameter replica, a paged KV pool,
and a :class:`~repro.serve.scheduler.Scheduler`.  Each :meth:`step` is one
decode-step boundary:

    1. fold   — buffered flood messages fold into θ (LiveUpdateBridge)
    2. admit  — queued requests claim slots + pages; one jitted prefill
                per distinct (batch, prompt-length) scatters their KV
    3. decode — one jitted paged-decode dispatch at the current page
                bucket emits a token for every active slot
    4. evict  — finished slots free their pages back to the queue

Compiled programs are cached per shape key — (Bg, T) for prefill, bucket
for decode — so a long-running server converges to a handful of traces
(``stats()["programs"]`` counts them).  Each phase of a step runs under a
host span (``repro.obs.span``: ``server.fold``, ``server.admit``,
``server.prefill``, ``server.decode``, and ``server.sample`` for the loop
that turns a dispatch's logits into tokens), so a profile names what the
host was doing while the device waited.
No buffer donation anywhere: simulated servers may share a params tree
(and on CPU donation is a no-op with warnings), and the live-update parity
oracle compares against the undonated monolithic path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import InputShape
from repro.launch import steps as steplib
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.serve.bridge import LiveUpdateBridge
from repro.serve.scheduler import Request, Scheduler, ServeConfig


class DecodeServer:
    """Continuous-batching token server for one (possibly churning) node."""

    def __init__(self, cfg, params, serve: ServeConfig, *, mesh=None,
                 pod=None, bridge: LiveUpdateBridge | None = None):
        tf.check_paged_support(cfg)
        self.cfg = cfg
        self.serve = serve
        self.mesh = mesh if mesh is not None else make_host_mesh(1, 1)
        self.pod = pod if pod is not None else steplib.PodConfig(
            param_dtype=serve.param_dtype)
        self.bridge = bridge
        self.params = params
        with self.mesh:
            self.pool = tf.init_paged_pool(cfg, serve.n_pages,
                                           serve.page_size, serve.param_dtype)
        self.sched = Scheduler(serve)
        self.results: dict[int, list[int]] = {}
        self._prefill_fns: dict[tuple[int, int], object] = {}
        self._decode_fns: dict[int, object] = {}
        self.n_steps = 0
        self.n_prefills = 0
        self.n_decodes = 0
        self.n_suspends = 0

    # -- request intake -------------------------------------------------------

    def submit(self, req: Request) -> None:
        if req.rid in self.results:
            raise ValueError(f"duplicate request id {req.rid}")
        self.results[req.rid] = []
        self.sched.submit(req)

    # -- compiled-program cache -----------------------------------------------

    def _prefill_fn(self, Bg: int, T: int):
        fn = self._prefill_fns.get((Bg, T))
        if fn is None:
            shape = InputShape("serve", T, Bg, "prefill")
            step, _, in_sh, out_sh = steplib.build_paged_prefill_step(
                self.cfg, shape, self.mesh, self.pod,
                page_size=self.serve.page_size,
                pages_per_req=self.serve.pages_per_req,
                n_pages=self.serve.n_pages)
            fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            self._prefill_fns[(Bg, T)] = fn
        return fn

    def _decode_fn(self, bucket: int):
        fn = self._decode_fns.get(bucket)
        if fn is None:
            shape = InputShape("serve", bucket * self.serve.page_size,
                               self.serve.max_batch, "decode")
            step, _, in_sh, out_sh = steplib.build_paged_decode_step(
                self.cfg, shape, self.mesh, self.pod,
                page_size=self.serve.page_size, pages_per_req=bucket,
                n_pages=self.serve.n_pages)
            fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            self._decode_fns[bucket] = fn
        return fn

    # -- sampling -------------------------------------------------------------

    def _sample(self, logits_row, rid: int, emit_pos: int) -> int:
        """Token for one slot's logits.  ``emit_pos`` is the absolute
        position the sampled token will occupy — (rid, emit_pos) keys the
        PRNG stream, so a run is deterministic and churn-replayable."""
        if self.serve.sampling == "greedy":
            return int(jnp.argmax(logits_row))
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.serve.sample_seed),
                               rid), emit_pos)
        return int(jax.random.categorical(
            key, logits_row / self.serve.temperature))

    # -- one decode-step boundary ---------------------------------------------

    def step(self) -> None:
        if self.sched.done:
            return
        self.n_steps += 1
        if self.bridge is not None and self.bridge.pending:
            with obs.span("server.fold"):
                self.params = self.bridge.fold(self.params)
        with obs.span("server.admit"):
            admitted = self.sched.admit()
            groups: dict[int, list[tuple[int, Request]]] = {}
            for slot, req in admitted:
                groups.setdefault(len(req.prompt), []).append((slot, req))
        for T in sorted(groups):
            self._prefill_group(T, groups[T])
        if self.sched.active_slots():
            self._decode_once()

    def _prefill_group(self, T: int, group: list[tuple[int, Request]]):
        Bg = len(group)
        tokens = np.stack([r.prompt for _, r in group])
        table = np.stack([self.sched.alloc.table[s] for s, _ in group])
        fn = self._prefill_fn(Bg, T)
        with obs.span("server.prefill"), self.mesh:
            last, self.pool = fn(self.params, self.pool,
                                 jnp.asarray(tokens), jnp.asarray(table))
        self.n_prefills += 1
        with obs.span("server.sample"):
            for i, (slot, req) in enumerate(group):
                # prefill emits the token at position len(prompt) == slot.pos
                tok = self._sample(last[i], req.rid,
                                   self.sched.slots[slot].pos)
                self.results[req.rid].append(tok)
                self.sched.record_emit(slot, tok)

    def _decode_once(self):
        bucket = self.sched.decode_bucket()
        tokens, pos, table = self.sched.decode_inputs()
        fn = self._decode_fn(bucket)
        with obs.span("server.decode"), self.mesh:
            logits, self.pool = fn(self.params, self.pool,
                                   jnp.asarray(tokens), jnp.asarray(table),
                                   jnp.asarray(pos))
        self.n_decodes += 1
        with obs.span("server.sample"):
            for slot in self.sched.active_slots():
                s = self.sched.slots[slot]
                # the decode wrote position s.pos; its token lands at s.pos + 1
                tok = self._sample(logits[slot], s.req.rid, s.pos + 1)
                self.results[s.req.rid].append(tok)
                if not self.sched.record_emit(slot, tok):
                    self.sched.advance(slot)

    # -- churn ----------------------------------------------------------------

    def suspend(self) -> int:
        """Node leaves mid-decode: every in-flight request is captured from
        its slot and page table as a resume request — prompt = tokens
        written so far, budget = remaining — and re-queued at the FRONT in
        slot order; its pages return to the free list.  On rejoin the
        normal admit path re-reserves pages and a re-prefill of the
        accumulated sequence resumes decode (the weights catch up
        separately, through anti-entropy into the bridge)."""
        n = 0
        for slot in reversed(self.sched.active_slots()):
            s = self.sched.slots[slot]
            emitted = s.req.max_new - s.remaining
            out = self.results[s.req.rid]
            toks = np.asarray(out[len(out) - emitted:], np.int32)
            seq = np.concatenate([s.req.prompt, toks]) if emitted \
                else s.req.prompt
            self.sched.release_slot(slot)
            self.sched.queue.appendleft(
                Request(rid=s.req.rid, prompt=seq, max_new=s.remaining))
            n += 1
        self.n_suspends += n
        return n

    # -- driver ---------------------------------------------------------------

    def run(self, max_steps: int = 10_000) -> dict[int, list[int]]:
        steps = 0
        while not self.sched.done:
            if steps >= max_steps:
                raise RuntimeError(
                    f"serve loop still busy after {max_steps} steps "
                    f"({len(self.sched.queue)} queued, "
                    f"{len(self.sched.active_slots())} active)")
            self.step()
            steps += 1
        return self.results

    def stats(self) -> dict:
        out = {"steps": self.n_steps, "prefills": self.n_prefills,
               "decodes": self.n_decodes, "suspends": self.n_suspends,
               "programs": len(self._prefill_fns) + len(self._decode_fns),
               "evicted": self.sched.n_evicted,
               "queued": len(self.sched.queue),
               "active": len(self.sched.active_slots()),
               "emitted": sum(len(v) for v in self.results.values())}
        if self.bridge is not None:
            out["bridge"] = self.bridge.stats()
        return out
