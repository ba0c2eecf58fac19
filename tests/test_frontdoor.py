"""serving/frontdoor/: router, radix prefix cache, speculative decode.

The load-bearing assertions mirror tests/test_serving.py's contract:
greedy-token parity against batch ``generate()`` regardless of which
front-door feature is on — a prefix-hit prompt that skipped prefill and
a speculative round that drafted badly must both emit the exact tokens
the plain engine would have.  On top of that: pager refcount
interleavings (shared prefix blocks survive the owner's release),
prefix-cache match/insert/evict mechanics, router placement/failover,
and the stale-snapshot placement guard.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import llama
from horovod_tpu.serving.frontdoor import (LocalReplica, PrefixCache,
                                           Router, RouterConfig)
from horovod_tpu.serving.frontdoor.transport import (DEAD_SIGNALS,
                                                     signals_from_snapshot)
from horovod_tpu.serving.kv_pager import KVPager, PagedKVCache

N = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()            # v256 d64 L2 H4 KV2 fp32
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(rng, lens):
    return [rng.randint(0, 256, size=(n,)).astype(np.int32) for n in lens]


def _oracle(params, cfg, prompt, max_new):
    full = np.asarray(llama.generate(
        params, jnp.asarray(prompt[None]), cfg, max_new_tokens=max_new))[0]
    return [int(t) for t in full[len(prompt):]]


def _pager(num_blocks=16, block_size=4):
    return KVPager(PagedKVCache(n_layers=2, num_blocks=num_blocks,
                                block_size=block_size,
                                rows=((2, 8), (2, 8))))


# ---------------------------------------------------------------------------
# pager refcounts (the substrate prefix sharing stands on)
# ---------------------------------------------------------------------------

def test_pager_shared_prefix_refcounts():
    p = _pager()
    t1 = p.allocate(1, 8)                     # 2 blocks, refcount 1 each
    p.pin(t1[0])
    assert p.refcount(t1[0]) == 2 and p.is_pinned(t1[0])
    p.check_invariants()
    # Second request adopts the pinned block as its prefix head.
    t2 = p.allocate(2, 8, prefix_blocks=[t1[0]])
    assert t2[0] == t1[0] and p.refcount(t1[0]) == 3
    assert p.shared_blocks() >= 1
    p.check_invariants()
    # Owner releases: shared block survives (cache + req 2 still hold it).
    p.release(1)
    assert p.refcount(t1[0]) == 2
    p.check_invariants()
    # Req 2 releases: only the pin holds it; still not reusable.
    free_before = p.free_blocks
    p.release(2)
    assert p.refcount(t1[0]) == 1 and p.free_blocks > free_before
    p.check_invariants()
    # Unpin drops it to the free list.
    free_before = p.free_blocks
    p.unpin(t1[0])
    assert p.refcount(t1[0]) == 0 and p.free_blocks == free_before + 1
    p.check_invariants()


def test_pager_truncate_keeps_shared_blocks():
    p = _pager()
    t1 = p.allocate(1, 8)
    for b in t1:
        p.pin(b)
    t2 = p.allocate(2, 12, prefix_blocks=t1)   # 2 shared + 1 private
    p.check_invariants()
    # Truncating below the shared region must decref, not free, the
    # shared tail block.
    remaining = p.truncate(2, 4)               # down to 1 block
    assert remaining == t2[:1]
    # Shared tail block decrefs (pin + req 1 remain) instead of freeing;
    # the private block goes straight back to the pool.
    assert p.refcount(t1[1]) == 2
    assert p.refcount(t2[2]) == 0
    p.check_invariants()
    p.release(1)
    p.release(2)
    for b in t1:
        p.unpin(b)
    p.check_invariants()
    assert p.free_blocks == p.cache.num_blocks - 1   # all but scratch


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------

def test_prefix_cache_match_insert():
    p = _pager()
    pc = PrefixCache(p)
    toks = np.arange(11, dtype=np.int32)       # 2 full blocks + tail
    table = p.allocate(1, 11)
    assert pc.insert(toks, table) == 2
    assert pc.resident_blocks == 2
    assert p.is_pinned(table[0]) and p.is_pinned(table[1])
    # Exact prefix hit, capped at the full blocks.
    n, blocks = pc.match(toks)
    assert n == 8 and blocks == table[:2]
    # A diverging second block only matches the first.
    other = toks.copy()
    other[5] = 99
    n, blocks = pc.match(other)
    assert n == 4 and blocks == table[:1]
    # match() never returns the whole prompt: >= 1 token must prefill.
    n, blocks = pc.match(toks[:8])
    assert n == 4 and blocks == table[:1]
    # Unrelated prompt: miss.
    n, blocks = pc.match(np.full(9, 200, np.int32))
    assert (n, blocks) == (0, [])
    # Re-inserting a matched path adds nothing.
    assert pc.insert(toks, table) == 0


def test_prefix_cache_lru_eviction():
    p = _pager()
    pc = PrefixCache(p)
    t1 = p.allocate(1, 4)
    t2 = p.allocate(2, 4)
    pc.insert(np.arange(4, dtype=np.int32), t1)
    pc.insert(np.arange(50, 54, dtype=np.int32), t2)
    p.release(1)
    p.release(2)
    # Refresh t2's stamp: t1's node becomes the LRU leaf.
    pc.match(np.arange(50, 55, dtype=np.int32))
    free_before = p.free_blocks
    assert pc.evict(1) == 1
    assert p.free_blocks == free_before + 1
    assert pc.resident_blocks == 1
    n, _ = pc.match(np.arange(5, dtype=np.int32))
    assert n == 0                              # t1's entry is gone
    n, _ = pc.match(np.arange(50, 55, dtype=np.int32))
    assert n == 4                              # t2's survived
    # Protected and still-referenced blocks are not evictable.
    assert pc.evict(1, protect=t2) == 0
    p.check_invariants()


def test_prefix_cache_respects_live_references():
    p = _pager()
    pc = PrefixCache(p)
    t1 = p.allocate(1, 4)
    pc.insert(np.arange(4, dtype=np.int32), t1)
    # Request 1 still holds the block: refcount 2, not evictable.
    assert pc.evict(1) == 0
    p.release(1)
    assert pc.evict(1) == 1
    p.check_invariants()


def test_prefix_cache_max_blocks_cap():
    p = _pager(num_blocks=32)
    pc = PrefixCache(p, max_blocks=2)
    t1 = p.allocate(1, 8)
    pc.insert(np.arange(8, dtype=np.int32), t1)
    p.release(1)
    assert pc.resident_blocks == 2
    # Inserting 2 more blocks under a 2-block cap evicts the old pair.
    t2 = p.allocate(2, 8)
    pc.insert(np.arange(100, 108, dtype=np.int32), t2)
    p.release(2)
    assert pc.resident_blocks == 2
    n, _ = pc.match(np.arange(9, dtype=np.int32))
    assert n == 0
    p.check_invariants()


# ---------------------------------------------------------------------------
# engine parity: prefix reuse and speculative decode
# ---------------------------------------------------------------------------

def test_prefix_reuse_greedy_parity(tiny):
    cfg, params = tiny
    sess = serving.serve(params, cfg, num_blocks=64, block_size=8,
                         max_active=4, use_flash="never",
                         prefix_cache=True)
    rng = np.random.RandomState(3)
    head = rng.randint(0, 256, size=(24,)).astype(np.int32)
    tails = _prompts(rng, [7, 11])
    prompts = [head] + [np.concatenate([head, t]) for t in tails]
    # First request populates the cache; the follow-ups (admitted after
    # it prefilled) hit its 3 full head blocks.
    futs = [sess.submit(prompts[0], 12)]
    sess.drain()
    futs += [sess.submit(p, 12) for p in prompts[1:]]
    sess.drain()
    for p, f in zip(prompts, futs):
        res = f.result()
        assert res.tokens == _oracle(params, cfg, p, 12), \
            "prefix-hit prompt diverged from the dense oracle"
    # The shared 24-token head (3 full blocks) was served from cache.
    m2 = futs[1].result().metrics
    assert m2["cached_tokens"] == 24
    assert futs[0].result().metrics["cached_tokens"] == 0
    sess.engine.pager.check_invariants()
    sess.close()


@pytest.mark.parametrize("k", [
    pytest.param(1, marks=pytest.mark.slow),
    2,
    pytest.param(4, marks=pytest.mark.slow),
])
def test_spec_decode_greedy_parity(tiny, k):
    """Draft == target: every draft agrees, yet emitted tokens must be
    the target's regardless (greedy spec decode is an exactness
    transform, not an approximation)."""
    cfg, params = tiny
    sess = serving.serve(params, cfg, num_blocks=64, block_size=8,
                         max_active=4, use_flash="never",
                         spec_k=k, draft_params=params, draft_cfg=cfg)
    prompts = _prompts(np.random.RandomState(4), [5, 9, 13])
    futs = [sess.submit(p, 11) for p in prompts]
    sess.drain()
    for p, f in zip(prompts, futs):
        assert f.result().tokens == _oracle(params, cfg, p, 11)
    # An identical draft must be accepted every time; anything below 1.0
    # means the draft pool diverged from the target pool (e.g. a draft
    # K/V position left unwritten after a fully-accepted round).
    spec = sess.engine.spec
    assert spec._drafted_total > 0
    assert spec._accepted_total == spec._drafted_total
    sess.engine.pager.check_invariants()
    sess.close()


@pytest.mark.slow
def test_spec_decode_weak_draft_parity(tiny):
    """A garbage draft model costs acceptance rate, never correctness."""
    cfg, params = tiny
    weak = llama.init_params(cfg, jax.random.PRNGKey(7))
    sess = serving.serve(params, cfg, num_blocks=64, block_size=8,
                         max_active=4, use_flash="never",
                         spec_k=3, draft_params=weak, draft_cfg=cfg)
    prompts = _prompts(np.random.RandomState(5), [6, 10])
    futs = [sess.submit(p, 10) for p in prompts]
    sess.drain()
    for p, f in zip(prompts, futs):
        assert f.result().tokens == _oracle(params, cfg, p, 10)
    sess.engine.pager.check_invariants()
    sess.close()


@pytest.mark.slow
def test_spec_with_prefix_cache_parity(tiny):
    cfg, params = tiny
    sess = serving.serve(params, cfg, num_blocks=64, block_size=8,
                         max_active=4, use_flash="never",
                         prefix_cache=True, spec_k=2,
                         draft_params=params, draft_cfg=cfg)
    rng = np.random.RandomState(6)
    head = rng.randint(0, 256, size=(16,)).astype(np.int32)
    prompts = [head, np.concatenate([head, _prompts(rng, [5])[0]])]
    futs = [sess.submit(prompts[0], 9)]
    sess.drain()
    futs.append(sess.submit(prompts[1], 9))
    sess.drain()
    for p, f in zip(prompts, futs):
        assert f.result().tokens == _oracle(params, cfg, p, 9)
    assert futs[1].result().metrics["cached_tokens"] == 16
    sess.engine.pager.check_invariants()
    sess.close()


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def _local_replicas(cfg, params, n=2, **kw):
    sessions = [serving.serve(params, cfg, num_blocks=64, block_size=8,
                              max_active=4, use_flash="never", **kw)
                for _ in range(n)]
    return [LocalReplica(str(i), s) for i, s in enumerate(sessions)]


@pytest.mark.slow
def test_router_balances_and_parity(tiny):
    cfg, params = tiny
    reps = _local_replicas(cfg, params)
    router = Router(reps, RouterConfig(affinity_tokens=0))
    prompts = _prompts(np.random.RandomState(8), [5, 6, 7, 8, 9, 10])
    futs = [router.submit(p, 8) for p in prompts]
    router.drain(timeout_s=120)
    placed = {r.replica_id: 0 for r in reps}
    for p, f in zip(prompts, futs):
        res = f.result(timeout=1)
        assert res.tokens == _oracle(params, cfg, p, 8)
        assert res.metrics["finish_reason"] == "length"
        placed[res.metrics["replica"]] += 1
    # Least-loaded placement with equal replicas splits the stream.
    assert placed["0"] == 3 and placed["1"] == 3, placed
    for r in reps:
        r.session.close()


def test_router_affinity_stickiness(tiny):
    cfg, params = tiny
    reps = _local_replicas(cfg, params)
    router = Router(reps, RouterConfig(affinity_tokens=4))
    rng = np.random.RandomState(9)
    head = rng.randint(0, 256, size=(6,)).astype(np.int32)
    same = [np.concatenate([head, t]) for t in _prompts(rng, [3, 4, 5])]
    futs = [router.submit(p, 4) for p in same]
    router.drain(timeout_s=120)
    replicas = {f.result(timeout=1).metrics["replica"] for f in futs}
    assert len(replicas) == 1, \
        "shared-prefix requests should stick to one replica"
    for r in reps:
        r.session.close()


@pytest.mark.slow
def test_router_failover_completes_on_survivor(tiny):
    cfg, params = tiny
    reps = _local_replicas(cfg, params)
    router = Router(reps, RouterConfig(affinity_tokens=0))
    prompts = _prompts(np.random.RandomState(10), [5, 6, 7, 8])
    streamed: dict[int, list[int]] = {}

    def cb_for(i):
        return lambda rid, t: streamed.setdefault(i, []).append(int(t))

    futs = [router.submit(p, 10, stream_cb=cb_for(i))
            for i, p in enumerate(prompts)]
    # Let everything get placed and emit a few tokens, then crash one.
    for _ in range(6):
        router.pump()
    reps[1].kill()
    router.drain(timeout_s=120)
    assert router.failovers >= 1
    for i, (p, f) in enumerate(zip(prompts, futs)):
        res = f.result(timeout=1)
        assert res.tokens == _oracle(params, cfg, p, 10)
        assert res.metrics["finish_reason"] == "length"
        # At-least-once streaming: a failed-over request replays from
        # token 0 (greedy decode is deterministic, so the replay is
        # identical); the stream's tail is always the result tokens.
        assert streamed[i][-len(res.tokens):] == res.tokens
    moved = [f.result(timeout=1).metrics for f in futs
             if f.result(timeout=1).metrics["router_attempts"] > 1]
    assert moved and all(m["replica"] == "0" for m in moved)
    reps[0].session.close()


def test_router_all_dead_queues_then_times_out(tiny):
    """With every replica dead the router queues rather than rejects (a
    drain window should delay, not drop); drain surfaces the stall as a
    TimeoutError and the flight stays unresolved for a replica that
    might come back."""
    cfg, params = tiny
    reps = _local_replicas(cfg, params, n=1)
    router = Router(reps, RouterConfig(max_attempts=2,
                                       failover_grace_s=0.0))
    fut = router.submit(np.arange(5, dtype=np.int32), 4)
    reps[0].kill()
    with pytest.raises(TimeoutError):
        router.drain(timeout_s=0.5)
    assert not fut.done()
    assert router.failovers >= 1               # it did try to move it
    reps[0].session.close()


# ---------------------------------------------------------------------------
# placement signals: staleness guard
# ---------------------------------------------------------------------------

def _frozen_snapshot(rank, age_s, interval_s=0.5, ready=True):
    return {
        "rank": rank, "time": time.time() - age_s,
        "meta": {"interval_s": interval_s},
        "snapshot": [
            {"name": "hvd_replica_ready", "type": "gauge",
             "samples": [{"labels": {}, "value": 1.0 if ready else 0.0}]},
            {"name": "hvd_serving_queue_depth", "type": "gauge",
             "samples": [{"labels": {}, "value": 1.0}]},
        ],
    }


def test_signals_stale_snapshot_marked():
    from horovod_tpu.obs.aggregate import snapshot_is_stale
    fresh = _frozen_snapshot(0, age_s=0.1)
    stale = _frozen_snapshot(1, age_s=5.0)
    assert not snapshot_is_stale(fresh)
    assert snapshot_is_stale(stale)            # 5s >> 2 x 0.5s interval
    s = signals_from_snapshot(stale)
    assert s["stale"] and s["alive"] and s["ready"]
    assert not signals_from_snapshot(fresh)["stale"]


def test_router_skips_stale_replica():
    """A replica whose publisher froze (snapshot older than twice its
    publish interval) must not take NEW placements, even though its
    last-known signals look healthy."""

    class FakeReplica:
        def __init__(self, rid, sig):
            self.replica_id = rid
            self._sig = sig
            self.submitted = []

        def drive(self):
            pass

        def signals(self):
            return dict(self._sig)

        def submit(self, prompt, max_tokens, *, eos_token=None,
                   trace_ctx=None):
            self.submitted.append(list(prompt))
            return len(self.submitted) - 1

        def partial_tokens(self, h):
            return []

        def result(self, h):
            return {"ok": True, "tokens": [1, 2],
                    "finish_reason": "length", "metrics": {}}

    fresh = signals_from_snapshot(_frozen_snapshot(0, age_s=0.1))
    stale = signals_from_snapshot(_frozen_snapshot(1, age_s=5.0))
    stale["queue_depth"] = 0.0                 # tempting, but frozen
    r_ok = FakeReplica("0", fresh)
    r_stale = FakeReplica("1", stale)
    router = Router([r_ok, r_stale], RouterConfig(affinity_tokens=0))
    futs = [router.submit(np.arange(4, dtype=np.int32), 2)
            for _ in range(4)]
    router.drain(timeout_s=10)
    assert len(r_stale.submitted) == 0
    assert len(r_ok.submitted) == 4
    assert all(f.result(timeout=1).tokens == [1, 2] for f in futs)


def test_dead_signals_never_place():
    class DeadReplica:
        replica_id = "0"

        def drive(self):
            pass

        def signals(self):
            return dict(DEAD_SIGNALS)

        def submit(self, *a, **kw):
            raise AssertionError("placed on a dead replica")

        def partial_tokens(self, h):
            return []

        def result(self, h):
            return None

    router = Router([DeadReplica()], RouterConfig(max_attempts=1))
    fut = router.submit(np.arange(3, dtype=np.int32), 2)
    for _ in range(5):
        router.pump()
    assert not fut.done() or fut.exception() is not None


# ---------------------------------------------------------------------------
# scheduler integration: cache eviction as a pressure valve
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_scheduler_evicts_cache_under_pressure(tiny):
    """A full pool with idle cached blocks must evict them to admit new
    work instead of rejecting or preempting."""
    cfg, params = tiny
    sess = serving.serve(params, cfg, num_blocks=10, block_size=8,
                         max_active=2, use_flash="never",
                         prefix_cache=True)
    rng = np.random.RandomState(11)
    p1 = rng.randint(0, 256, size=(16,)).astype(np.int32)
    f1 = sess.submit(p1, 4)
    sess.drain()
    assert f1.result().metrics["finish_reason"] == "length"
    cache = sess.engine.prefix_cache
    assert cache.resident_blocks == 2          # p1's two full blocks
    probe = np.concatenate([p1, p1[:1]])
    assert cache.match(probe)[0] == 16
    # 9 usable blocks, 2 pinned idle: a 60-token prompt needs 8 blocks
    # (decode headroom included) — only an eviction makes it fit.
    p2 = rng.randint(0, 256, size=(60,)).astype(np.int32)
    f2 = sess.submit(p2, 4)
    sess.drain()
    assert f2.result().tokens == _oracle(params, cfg, p2, 4)
    assert cache.match(probe)[0] < 16          # p1's chain shrank
    sess.engine.pager.check_invariants()
    sess.close()
