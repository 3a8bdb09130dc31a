"""repro.serve: continuous-batching decode over live seed-reconstructed
weights (DESIGN.md §10).

Three pinned oracles:

1. **Stub parity** — the paged continuous-batching server reproduces the
   monolithic ``launch/serve.py`` greedy token stream bitwise, including
   when the batch is squeezed through fewer slots than requests
   (eviction + free-list reuse + staggered admission).
2. **Live-update parity** — decoding while folding flood messages at
   decode-step boundaries equals offline-folding the same messages into
   the weights at the same boundaries and decoding monolithically —
   including a fold whose messages cross a τ-refresh boundary
   (epoch-grouped, sender-step rule).
3. **Churn replay** — a trainers+servers swarm with leave/rejoin churn on
   the virtual clock is a pure function of its script: running it twice
   gives identical token streams AND an identical byte ledger.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import archs
from repro.configs.base import InputShape
from repro.core.seeds import client_seed
from repro.core.subcge import SubCGEConfig
from repro.launch import steps as steplib
from repro.launch.mesh import make_host_mesh
from repro.models import params as plib
from repro.models import transformer as tf
from repro.serve import (DecodeServer, LiveUpdateBridge, PageAllocator,
                         Request, Scheduler, ServeConfig, ServeSwarmSim,
                         bucket_pages, pages_needed)
from repro.topology.dynamic import ChurnSchedule

B, PL, NEW = 4, 12, 4
CAP = PL + NEW


@pytest.fixture(scope="module")
def cfg():
    return archs.reduced(archs.get("tinyllama-1.1b"))


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


@pytest.fixture(scope="module")
def pod():
    return steplib.PodConfig(param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return plib.init_params(tf.arch_spec(cfg), 0, jnp.float32)


@pytest.fixture(scope="module")
def prompts(cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(0), (B, PL), 0,
                                         cfg.vocab), np.int32)


def _monolithic_stream(cfg, mesh, pod, params, prompts, fold_at=None):
    """The exact launch/serve.py greedy loop (pre-paging): eager prefill over
    a monolithic cache, then jitted single-position decode.  ``fold_at``
    maps decode-step index -> params to switch to AT that step boundary
    (index 0 = before prefill) for the live-update oracle."""
    n_req = prompts.shape[0]
    dshape = InputShape("serve", CAP, n_req, "decode")
    decode, _, in_sh, out_sh = steplib.build_decode_step(cfg, dshape, mesh,
                                                         pod)
    fold_at = fold_at or {}
    with mesh:
        p = fold_at.get(0, params)
        cache = tf.init_cache(cfg, n_req, CAP, jnp.float32)
        logits, cache, _ = tf.forward(cfg, p, {"tokens": jnp.asarray(prompts)},
                                      cache=cache, pos=0)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        decode_j = jax.jit(decode, in_shardings=in_sh, out_shardings=out_sh)
        out = [tok]
        for i in range(NEW - 1):
            p = fold_at.get(i + 1, p)
            lg, cache = decode_j(p, cache, tok, jnp.int32(PL + i))
            tok = jnp.argmax(lg, axis=-1)[:, None]
            out.append(tok)
    return np.concatenate([np.asarray(t) for t in out], axis=1)


@pytest.fixture(scope="module")
def ref_stream(cfg, mesh, pod, params, prompts):
    return _monolithic_stream(cfg, mesh, pod, params, prompts)


# ---------------------------------------------------------------------------
# host-side units: page allocator, buckets, scheduler, config
# ---------------------------------------------------------------------------

def test_pages_needed_and_buckets():
    assert pages_needed(1, 4) == 1 and pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2 and pages_needed(16, 4) == 4
    assert bucket_pages(1, 8) == 1
    assert bucket_pages(3, 8) == 4          # pow2 round-up
    assert bucket_pages(5, 6) == 6          # capped at pages_per_req
    assert bucket_pages(0, 8) == 1


def test_page_allocator_reserve_release_reuse():
    a = PageAllocator(n_pages=8, page_size=4, max_batch=2, pages_per_req=4)
    assert a.dump == 8 and a.free_pages == 8
    p0 = a.alloc(0, 3)
    assert p0 == [0, 1, 2] and a.pages_in_use == 3
    assert list(a.table[0]) == [0, 1, 2, 8]     # tail holds the dump id
    with pytest.raises(ValueError):
        a.alloc(0, 1)                           # slot already occupied
    p1 = a.alloc(1, 4)
    assert p1 == [3, 4, 5, 6]
    assert not a.can_alloc(2) and a.can_alloc(1)
    with pytest.raises(ValueError):
        a.alloc(0, 2)                           # only 1 page free
    assert a.release(0) == [0, 1, 2]
    assert list(a.table[0]) == [8, 8, 8, 8]
    # freed pages are reused lowest-first, in the released order
    assert a.alloc(0, 2) == [0, 1]


def test_page_allocator_rejects_undersized_pool():
    with pytest.raises(ValueError):
        PageAllocator(n_pages=3, page_size=4, max_batch=1, pages_per_req=4)


def test_serve_config_validation():
    assert ServeConfig().pages_per_req == 128 // 16
    with pytest.raises(ValueError):
        ServeConfig(sampling="nucleus")
    with pytest.raises(ValueError):
        ServeConfig(max_seq=100, page_size=16)  # not a page multiple
    with pytest.raises(ValueError):
        ServeConfig(sampling="temperature", temperature=0.0)


def test_scheduler_fifo_admission_and_eviction():
    cfg = ServeConfig(max_batch=2, page_size=4, n_pages=4, max_seq=16)
    s = Scheduler(cfg)
    with pytest.raises(ValueError):             # over max_seq
        s.submit(Request(rid=9, prompt=np.arange(13), max_new=4))
    s.submit(Request(rid=0, prompt=np.arange(6), max_new=2))   # 2 pages
    s.submit(Request(rid=1, prompt=np.arange(6), max_new=2))   # 2 pages
    s.submit(Request(rid=2, prompt=np.arange(2), max_new=2))   # 1 page
    admitted = s.admit()
    # head-of-line blocking: rid 2 (1 page) must NOT jump rid 1's budget
    assert [r.rid for _, r in admitted] == [0, 1]
    assert s.alloc.free_pages == 0
    assert [r.rid for r in s.queue] == [2]
    assert s.decode_bucket() == 2               # pos 6 -> 7 positions -> 2pg
    # finishing rid 0 frees its pages; rid 2 admits into the freed slot
    s.record_emit(0, 5)
    assert s.slots[0] is not None               # one token still owed
    s.record_emit(0, 7)
    assert s.slots[0] is None and s.n_evicted == 1
    admitted = s.admit()
    assert [(i, r.rid) for i, r in admitted] == [(0, 2)]
    assert not s.done
    s.record_emit(1, 1)
    s.record_emit(1, 1)
    s.record_emit(0, 1)
    s.record_emit(0, 1)
    assert s.done


# ---------------------------------------------------------------------------
# oracle 1: paged continuous batching == monolithic greedy stream
# ---------------------------------------------------------------------------

def test_paged_server_matches_monolithic_stream(cfg, mesh, pod, params,
                                                prompts, ref_stream):
    serve = ServeConfig(max_batch=B, page_size=4, n_pages=16, max_seq=CAP)
    srv = DecodeServer(cfg, params, serve, mesh=mesh, pod=pod)
    for b in range(B):
        srv.submit(Request(rid=b, prompt=prompts[b], max_new=NEW))
    results = srv.run()
    np.testing.assert_array_equal(
        np.array([results[b] for b in range(B)]), ref_stream)
    st = srv.stats()
    assert st["evicted"] == B and st["prefills"] == 1


def test_staggered_slots_still_match_monolithic(cfg, mesh, pod, params,
                                                prompts, ref_stream):
    """4 requests through 2 slots: the second wave admits into pages the
    first wave freed — eviction, free-list reuse and a second prefill, all
    without perturbing any token."""
    serve = ServeConfig(max_batch=2, page_size=4, n_pages=8, max_seq=CAP)
    srv = DecodeServer(cfg, params, serve, mesh=mesh, pod=pod)
    for b in range(B):
        srv.submit(Request(rid=b, prompt=prompts[b], max_new=NEW))
    results = srv.run()
    np.testing.assert_array_equal(
        np.array([results[b] for b in range(B)]), ref_stream)
    st = srv.stats()
    assert st["prefills"] == 2 and st["evicted"] == B


def test_duplicate_rid_rejected(cfg, mesh, pod, params, prompts):
    serve = ServeConfig(max_batch=2, page_size=4, n_pages=8, max_seq=CAP)
    srv = DecodeServer(cfg, params, serve, mesh=mesh, pod=pod)
    srv.submit(Request(rid=0, prompt=prompts[0], max_new=1))
    with pytest.raises(ValueError):
        srv.submit(Request(rid=0, prompt=prompts[1], max_new=1))


def test_temperature_sampling_is_deterministic(cfg, mesh, pod, params,
                                               prompts):
    def stream(seed):
        serve = ServeConfig(max_batch=B, page_size=4, n_pages=16,
                            max_seq=CAP, sampling="temperature",
                            temperature=5.0, sample_seed=seed)
        srv = DecodeServer(cfg, params, serve, mesh=mesh, pod=pod)
        for b in range(B):
            srv.submit(Request(rid=b, prompt=prompts[b], max_new=NEW))
        return np.array([srv.run()[b] for b in range(B)])

    a, b = stream(0), stream(0)
    np.testing.assert_array_equal(a, b)         # same seed -> same stream
    assert ((0 <= a) & (a < cfg.vocab)).all()
    assert not np.array_equal(a, stream(1))     # T=5.0 is nearly uniform


# ---------------------------------------------------------------------------
# oracle 2: live-update fold parity (incl. τ-refresh boundary)
# ---------------------------------------------------------------------------

def _msg_batch(gseed, steps):
    steps = np.asarray(steps, np.int32)
    seeds = np.array([client_seed(gseed, int(s), i % 2)
                      for i, s in enumerate(steps)], np.uint32)
    return seeds, np.full(steps.shape, 0.05, np.float32), steps


def test_decode_under_live_updates_matches_offline_fold(cfg, mesh, pod,
                                                        params, prompts,
                                                        ref_stream):
    scfg = SubCGEConfig(rank=4, refresh_period=2, eps=1e-3)
    gseed = 7
    b1 = _msg_batch(gseed, [0, 0, 1, 1])        # epochs {0}: one slot
    b2 = _msg_batch(gseed, [1, 2, 2, 3])        # epochs {0, 2}: crosses τ=2

    # offline reference: fold the same batches into the weights at the same
    # step boundaries (same jitted epoch-grouped apply), decode monolithic
    ref_bridge = LiveUpdateBridge(cfg, scfg, gseed, node=0)
    ref_bridge.ingest_arrays(*b1)
    p1 = ref_bridge.fold(params)
    ref_bridge.ingest_arrays(*b2)
    p2 = ref_bridge.fold(p1)
    ref = _monolithic_stream(cfg, mesh, pod, params, prompts,
                             fold_at={0: p1, 2: p2})
    assert not np.array_equal(ref, ref_stream)  # folds must move tokens

    serve = ServeConfig(max_batch=B, page_size=4, n_pages=16, max_seq=CAP)
    bridge = LiveUpdateBridge(cfg, scfg, gseed, node=0)
    srv = DecodeServer(cfg, params, serve, mesh=mesh, pod=pod, bridge=bridge)
    for b in range(B):
        srv.submit(Request(rid=b, prompt=prompts[b], max_new=NEW))
    bridge.ingest_arrays(*b1)
    srv.step()                                  # fold b1 -> prefill+decode 1
    bridge.ingest_arrays(*b2)
    srv.step()                                  # fold b2 -> decode 2
    srv.step()                                  # decode 3
    assert srv.sched.done
    np.testing.assert_array_equal(
        np.array([srv.results[b] for b in range(B)]), ref)
    assert bridge.stats() == {"messages_folded": 8, "n_folds": 2,
                              "pending": 0, "fold_programs": 2}


def test_step_spans_and_program_counts(cfg, mesh, pod, params, prompts,
                                      tmp_path):
    """One profiled step names its host phases in order; ``programs`` and
    ``fold_programs`` count the distinct compiled shapes, not the calls."""
    scfg = SubCGEConfig(rank=4, refresh_period=2, eps=1e-3)
    serve = ServeConfig(max_batch=2, page_size=4, n_pages=8, max_seq=CAP)
    bridge = LiveUpdateBridge(cfg, scfg, 7, node=0)
    srv = DecodeServer(cfg, params, serve, mesh=mesh, pod=pod, bridge=bridge)
    keys = []
    for attr in ("_prefill_fn", "_decode_fn", "_fold_fn"):
        owner = bridge if attr == "_fold_fn" else srv
        orig = getattr(owner, attr)

        def record(*key, orig=orig, attr=attr):
            keys.append((attr, key))
            return orig(*key)
        setattr(owner, attr, record)
    for b in range(B):
        srv.submit(Request(rid=b, prompt=prompts[b], max_new=NEW))
    bridge.ingest_arrays(*_msg_batch(7, [0, 0, 1]))
    with jax.profiler.trace(str(tmp_path)):
        srv.step()
    xplane = next(tmp_path.rglob("*.xplane.pb"))
    events = [ev for plane in jax.profiler.ProfileData.from_file(
        str(xplane)).planes for line in plane.lines for ev in line.events
        if ev.name.startswith("server.")]
    assert [ev.name for ev in sorted(events, key=lambda e: e.start_ns)] \
        == ["server.fold", "server.admit", "server.prefill", "server.sample",
            "server.decode", "server.sample"]
    bridge.ingest_arrays(*_msg_batch(7, [1, 2]))
    srv.run()
    calls = {a: [k for b, k in keys if b == a] for a, _ in keys}
    assert len(calls["_prefill_fn"]) > len(set(calls["_prefill_fn"]))
    st = srv.stats()
    assert st["programs"] == len(set(calls["_prefill_fn"])) \
        + len(set(calls["_decode_fn"]))
    assert st["bridge"]["fold_programs"] == len(set(calls["_fold_fn"])) == 2


def test_bridge_ingest_skips_inbox_padding():
    cfg = archs.reduced(archs.get("tinyllama-1.1b"))
    br = LiveUpdateBridge(cfg, SubCGEConfig(rank=4), 0, node=0)
    n = br.ingest_arrays(np.array([3, 0, 5], np.uint32),
                         np.array([0.1, 0.0, 0.2], np.float32),
                         np.array([0, -1, 2], np.int32))
    assert n == 2 and br.pending == 2           # the step=-1 row is padding


# ---------------------------------------------------------------------------
# oracle 3: churn replay determinism on the virtual clock
# ---------------------------------------------------------------------------

def test_churn_replay_is_deterministic(cfg):
    scfg = SubCGEConfig(rank=4, refresh_period=2, eps=1e-3)
    serve = ServeConfig(max_batch=2, page_size=4, n_pages=12, max_seq=20)
    sim_prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                                (4, 12), 0, cfg.vocab),
                             np.int32)

    def build():
        sim = ServeSwarmSim(cfg, scfg, serve, n_trainers=2, n_servers=2,
                            train_steps=6, global_seed=7,
                            churn=ChurnSchedule.leave_rejoin([3], 2, 4),
                            train_period=1.0, serve_period=0.5)
        for rid in range(4):
            sim.submit(2 if rid < 2 else 3,
                       Request(rid=rid, prompt=sim_prompts[rid], max_new=6))
        return sim

    a, b = build().run(), build().run()
    assert a["tokens"] == b["tokens"]
    assert a["ledger"] == b["ledger"]
    assert a["servers"] == b["servers"]
    # the churn actually bit: server 3 suspended mid-decode, re-prefilled
    # on rejoin, and caught its weights up through the flood
    assert a["servers"][3]["suspends"] == 2
    assert a["servers"][3]["prefills"] == 2
    assert a["servers"][3]["bridge"]["messages_folded"] > 0
    assert a["ledger"]["sync_bytes"] > 0        # anti-entropy was charged
    assert sorted(a["tokens"]) == [0, 1, 2, 3]
    assert all(len(t) == 6 for t in a["tokens"].values())


def test_churn_may_only_target_servers(cfg):
    scfg = SubCGEConfig(rank=4)
    serve = ServeConfig(max_batch=2, page_size=4, n_pages=8, max_seq=16)
    with pytest.raises(ValueError):
        ServeSwarmSim(cfg, scfg, serve, n_trainers=2, n_servers=1,
                      churn=ChurnSchedule.leave_rejoin([0], 1, 2))
