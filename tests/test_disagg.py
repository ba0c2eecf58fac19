"""serving/disagg/: cross-replica KV migration + pool-aware routing.

Deterministic CPU tests.  The load-bearing assertion is the same one
the colocated engine carries: greedy-token parity against batch
``generate()`` — here through a full export → publish → fetch → import
→ resume cycle across two engines, including the radix-partial-prefix
attach on either side, double imports, torn transports, and router
failover at every migration stage.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import llama
from horovod_tpu.obs import REGISTRY
from horovod_tpu.serving.disagg import (DictKV, DisaggRouter,
                                        DisaggRouterConfig,
                                        LocalDisaggReplica,
                                        MigrationUnavailable,
                                        delete_migration, fetch_migration,
                                        migration_published,
                                        publish_migration)
from horovod_tpu.serving.disagg import transport as mig_transport
from horovod_tpu.serving.kv_pager import KVPager, OutOfBlocks, PagedKVCache


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _oracle(params, cfg, prompt, max_new):
    full = np.asarray(llama.generate(
        params, jnp.asarray(np.asarray(prompt)[None]), cfg,
        max_new_tokens=max_new))[0]
    return [int(t) for t in full[len(prompt):]]


def _sess(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_active", 4)
    kw.setdefault("prefix_cache", True)
    return serving.serve(params, cfg, **kw)


def _export_one(sess, prompt, max_new, **submit_kw):
    """Run one prefill-export request to completion on ``sess`` and
    return (manifest, k_bytes, v_bytes, first_token)."""
    box = {}

    def grab(manifest, k_bytes, v_bytes):
        box["mig"] = (manifest, k_bytes, v_bytes)

    toks: list[int] = []
    fut = sess.submit(prompt, max_new, migrate_cb=grab,
                      stream_cb=lambda rid, t: toks.append(int(t)),
                      **submit_kw)
    sess.drain()
    res = fut.result(timeout=5)
    assert res.metrics["finish_reason"] == "migrated", res.metrics
    assert "mig" in box, "migrate_cb never ran"
    assert toks == list(res.tokens)
    return (*box["mig"], list(res.tokens))


def _counter_value(name, **labels):
    fam = REGISTRY.get(name)
    return fam.labels(**labels).value if labels else fam.value


# ---------------------------------------------------------------------------
# pager: export/import refcount interleavings (host-only, no jax)
# ---------------------------------------------------------------------------

def _pager(num_blocks=16, block_size=4):
    return KVPager(PagedKVCache(n_layers=2, num_blocks=num_blocks,
                                block_size=block_size,
                                rows=((2, 8), (2, 8))))


def test_pager_import_attach_bumps_refcounts():
    """An import that prefix-attaches an exporter's blocks must bump
    their refcounts — releasing either side alone keeps the pages."""
    p = _pager()
    t1 = p.allocate(1, 16)                    # 4 blocks (the "export")
    t2 = p.allocate(2, 17, prefix_blocks=t1[:2])   # import, 2 shared
    assert t2[:2] == t1[:2]
    assert p.refcount(t1[0]) == 2 and p.refcount(t1[1]) == 2
    assert p.refcount(t1[2]) == 1
    p.check_invariants()
    free_before = p.free_blocks
    p.release(1)                              # exporter finishes first
    # Only the two unshared blocks of t1 actually freed.
    assert p.free_blocks == free_before + 2
    assert p.refcount(t2[0]) == 1, "shared pages must survive the export"
    p.check_invariants()
    p.release(2)
    p.check_invariants()


def test_pager_truncate_keeps_shared_across_export():
    """Truncating the importer back to the shared boundary drops its
    references without freeing pages the exporter still holds."""
    p = _pager()
    t1 = p.allocate(1, 12)                    # 3 blocks
    t2 = p.allocate(2, 20, prefix_blocks=t1)  # 3 shared + 2 own
    assert all(p.refcount(b) == 2 for b in t1)
    kept = p.truncate(2, 8)                   # back to 2 blocks
    assert kept == t1[:2]
    assert p.refcount(t1[2]) == 1, \
        "truncate must decref, not free, a block the exporter holds"
    assert p.table(1) == t1, "exporter's table untouched"
    p.check_invariants()
    p.release(1)
    assert p.refcount(t1[0]) == 1, "importer still holds the prefix"
    p.check_invariants()


def test_pager_double_attach_is_refcounted_not_copied():
    """Two imports of the same exported prefix share the same physical
    pages at refcount 3 — idempotent attach, no duplication."""
    p = _pager()
    t1 = p.allocate(1, 16)
    free_after_first = None
    for rid in (2, 3):
        p.allocate(rid, 17, prefix_blocks=t1[:3])
        if free_after_first is None:
            free_after_first = p.free_blocks
    assert all(p.refcount(b) == 3 for b in t1[:3])
    # The second import consumed only its non-shared tail.
    assert free_after_first - p.free_blocks == 2
    p.check_invariants()
    for rid in (1, 2, 3):
        p.release(rid)
    assert p.free_blocks == p.cache.num_blocks - 1
    p.check_invariants()


# ---------------------------------------------------------------------------
# transport: publish/fetch, shared deadline, torn reads
# ---------------------------------------------------------------------------

def _fake_migration(n=512):
    manifest = {"schema": 1, "version": "7.1.8", "k_len": n, "v_len": n,
                "generated": [3], "context_len": 8, "n_blocks": 2}
    return manifest, bytes(range(256)) * (n // 256), b"\x01" * n


def test_transport_roundtrip_and_cleanup():
    kv = DictKV()
    manifest, k, v = _fake_migration()
    assert not migration_published(kv, "7.1")
    publish_migration(kv, "7.1", manifest, k, v)
    assert migration_published(kv, "7.1")
    m2, k2, v2 = fetch_migration(kv, "7.1", timeout_ms=2000)
    assert (m2, k2, v2) == (manifest, k, v)
    delete_migration(kv, "7.1")
    assert not migration_published(kv, "7.1")
    with pytest.raises(MigrationUnavailable):
        fetch_migration(kv, "7.1", timeout_ms=100)


def test_transport_publish_shares_one_deadline():
    """Every chunk of all three blobs draws on ONE deadline: the
    per-call budgets handed to kv_put_blob must be non-increasing and
    bounded by the overall budget — never chunks x timeout."""
    seen = []
    real = mig_transport.kv_put_blob

    def spy(kv, key, blob, **kw):
        seen.append(kw["deadline_s"])
        return real(kv, key, blob, **kw)

    manifest, k, v = _fake_migration()
    old = mig_transport.kv_put_blob
    mig_transport.kv_put_blob = spy
    try:
        publish_migration(DictKV(), "9.1", manifest, k, v,
                          deadline_s=5.0)
    finally:
        mig_transport.kv_put_blob = old
    assert len(seen) == 3
    assert all(d <= 5.0 for d in seen), seen
    assert seen == sorted(seen, reverse=True), \
        f"later blobs must see a smaller remaining budget: {seen}"


def test_transport_fetch_shares_one_deadline():
    seen = []
    real = mig_transport.kv_get_blob

    def spy(kv, key, timeout_ms=10000):
        seen.append(timeout_ms)
        return real(kv, key, timeout_ms=timeout_ms)

    kv = DictKV()
    manifest, k, v = _fake_migration()
    publish_migration(kv, "9.2", manifest, k, v)
    old = mig_transport.kv_get_blob
    mig_transport.kv_get_blob = spy
    try:
        fetch_migration(kv, "9.2", timeout_ms=4000)
    finally:
        mig_transport.kv_get_blob = old
    assert len(seen) == 4        # manifest, k, v, manifest re-read
    assert all(t <= 4000 for t in seen), seen
    assert seen == sorted(seen, reverse=True), seen


def test_transport_torn_payload_length_detected():
    kv = DictKV()
    manifest, k, v = _fake_migration()
    publish_migration(kv, "9.3", manifest, k, v)
    # Corrupt the K payload under an honest meta record: fewer bytes
    # arrive than the manifest promised.
    kv.set("fd/mig/9.3/k/0", k[: len(k) // 2])
    kv.set("fd/mig/9.3/k/meta", f"1:{len(k) // 2}".encode())
    with pytest.raises(MigrationUnavailable, match="torn"):
        fetch_migration(kv, "9.3", timeout_ms=2000)


def test_transport_version_flip_mid_fetch_detected():
    """A republish that lands between the payload fetch and the
    manifest re-read flips the version; the importer must refuse the
    spliced payloads."""
    import json

    class FlippingKV(DictKV):
        def __init__(self):
            super().__init__()
            self.manifest_reads = 0
            self.armed = False

        def wait(self, key, timeout_ms=10000):
            if self.armed and key == "fd/mig/9.4/manifest/0":
                self.manifest_reads += 1
                if self.manifest_reads >= 2:
                    m = dict(_fake_migration()[0], version="7.2.9")
                    blob = json.dumps(m, sort_keys=True).encode()
                    self.set("fd/mig/9.4/manifest/meta",
                             f"1:{len(blob)}".encode())
                    self.set(key, blob)
            return super().wait(key, timeout_ms)

    kv = FlippingKV()
    manifest, k, v = _fake_migration()
    publish_migration(kv, "9.4", manifest, k, v)
    kv.armed = True
    with pytest.raises(MigrationUnavailable, match="version flipped"):
        fetch_migration(kv, "9.4", timeout_ms=2000)


# ---------------------------------------------------------------------------
# engine: export -> import parity
# ---------------------------------------------------------------------------

def test_migrated_decode_matches_generate(tiny):
    """The headline contract: a request prefilled on engine A and
    decoded on engine B emits exactly the tokens an unmigrated run
    emits (greedy decode is deterministic)."""
    cfg, params = tiny
    rng = np.random.RandomState(21)
    prompt = rng.randint(0, cfg.vocab_size, size=(9,)).astype(np.int32)
    sess_a, sess_b = _sess(tiny), _sess(tiny)

    manifest, k_bytes, v_bytes, head = _export_one(sess_a, prompt, 12)
    assert len(head) == 1, "export runs right after the prefill emission"

    streamed: list[int] = []
    fut = sess_b.import_migrated(
        manifest, k_bytes, v_bytes,
        stream_cb=lambda rid, t: streamed.append(int(t)))
    sess_b.drain()
    res = fut.result(timeout=5)
    want = _oracle(params, cfg, prompt, 12)
    assert head + list(res.tokens)[1:] == want  # head == res.tokens[0]
    assert list(res.tokens) == want, (res.tokens, want)
    assert res.metrics["finish_reason"] == "length"
    # The importer streams only the continuation; the prefill token was
    # already streamed by the exporting replica.
    assert head + streamed == want, (head, streamed)


def test_migrated_decode_honors_eos(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(22)
    prompt = rng.randint(0, cfg.vocab_size, size=(7,)).astype(np.int32)
    want = _oracle(params, cfg, prompt, 10)
    eos = want[4]                 # force an early stop mid-continuation
    sess_a, sess_b = _sess(tiny), _sess(tiny)
    manifest, k_bytes, v_bytes, _ = _export_one(sess_a, prompt, 10,
                                                eos_token=eos)
    fut = sess_b.import_migrated(manifest, k_bytes, v_bytes)
    sess_b.drain()
    res = fut.result(timeout=5)
    assert res.metrics["finish_reason"] == "stop"
    assert list(res.tokens) == want[:5], (res.tokens, want)


def test_migrated_parity_with_radix_partial_prefix(tiny):
    """Both radix corners at once: the EXPORT side prefills through a
    warm prefix-cache hit (its table starts with shared pages), and the
    IMPORT side attaches the longest cached prefix locally instead of
    scattering those payload blocks."""
    cfg, params = tiny
    rng = np.random.RandomState(23)
    stem = rng.randint(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    prompt = np.concatenate(
        [stem, rng.randint(0, cfg.vocab_size, size=(5,))]).astype(np.int32)
    sess_a, sess_b = _sess(tiny), _sess(tiny)

    # Warm BOTH sides' radix caches with a request sharing the stem.
    for warm_sess in (sess_a, sess_b):
        warm_sess.submit(stem, 2)
        warm_sess.drain()

    manifest, k_bytes, v_bytes, head = _export_one(sess_a, prompt, 11)
    before = _counter_value("hvd_disagg_blocks_attached_total",
                            source="prefix_cache")
    fut = sess_b.import_migrated(manifest, k_bytes, v_bytes)
    attached = _counter_value("hvd_disagg_blocks_attached_total",
                              source="prefix_cache") - before
    assert attached >= 1, \
        "import must attach the warmed prefix shared, not re-scatter it"
    sess_b.drain()
    res = fut.result(timeout=5)
    want = _oracle(params, cfg, prompt, 11)
    assert list(res.tokens) == want, (res.tokens, want)
    sess_b.engine.pager.check_invariants()


def test_double_import_is_idempotent(tiny):
    """Importing the same manifest twice (a decode-replica failover
    races its own retry) yields two independent requests with identical
    tokens; the second attach prefix-shares the first's pages."""
    cfg, params = tiny
    rng = np.random.RandomState(24)
    prompt = rng.randint(0, cfg.vocab_size, size=(10,)).astype(np.int32)
    sess_a, sess_b = _sess(tiny), _sess(tiny)
    manifest, k_bytes, v_bytes, _ = _export_one(sess_a, prompt, 9)

    before = _counter_value("hvd_disagg_blocks_attached_total",
                            source="prefix_cache")
    fut1 = sess_b.import_migrated(manifest, k_bytes, v_bytes)
    fut2 = sess_b.import_migrated(manifest, k_bytes, v_bytes)
    attached = _counter_value("hvd_disagg_blocks_attached_total",
                              source="prefix_cache") - before
    assert attached >= 1, \
        "second import must attach the first import's pages shared"
    sess_b.drain()
    want = _oracle(params, cfg, prompt, 9)
    r1, r2 = fut1.result(timeout=5), fut2.result(timeout=5)
    assert list(r1.tokens) == want
    assert list(r2.tokens) == want, "double import must stay token-identical"
    sess_b.engine.pager.check_invariants()


def test_import_rejects_geometry_and_torn_payloads(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(25)
    prompt = rng.randint(0, cfg.vocab_size, size=(6,)).astype(np.int32)
    sess_a = _sess(tiny)
    manifest, k_bytes, v_bytes, _ = _export_one(sess_a, prompt, 6)

    other = _sess(tiny, block_size=8)
    with pytest.raises(ValueError, match="geometry"):
        other.engine.import_migrated(manifest, k_bytes, v_bytes)
    sess_b = _sess(tiny)
    with pytest.raises(ValueError, match="torn"):
        sess_b.engine.import_migrated(manifest, k_bytes[:-8], v_bytes)
    bad = dict(manifest, schema=99)
    with pytest.raises(ValueError, match="schema"):
        sess_b.engine.import_migrated(bad, k_bytes, v_bytes)
    # A healthy import still works after the rejects (no leaked state).
    fut = sess_b.import_migrated(manifest, k_bytes, v_bytes)
    sess_b.drain()
    assert list(fut.result(timeout=5).tokens) == \
        _oracle(params, cfg, prompt, 6)
    sess_b.engine.pager.check_invariants()


def test_migration_manifest_carries_one_connected_trace(tiny):
    """Regression: the decode-side import must ADOPT the manifest's
    trace context — same trace_id across export and import, the
    imported root parented under the exporting request's span — instead
    of opening a fresh orphan trace."""
    cfg, params = tiny
    rng = np.random.RandomState(27)
    prompt = rng.randint(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    sess_a, sess_b = _sess(tiny), _sess(tiny)
    manifest, k_bytes, v_bytes, _ = _export_one(sess_a, prompt, 8)
    assert manifest.get("trace", {}).get("sampled") is True, manifest
    tid = manifest["trace"]["trace_id"]
    fut = sess_b.import_migrated(manifest, k_bytes, v_bytes)
    sess_b.drain()
    fut.result(timeout=5)
    from horovod_tpu.obs import trace as obs_trace
    exp = obs_trace.TRACER.export(tid)
    assert exp is not None, "the adopted trace must finish under the " \
        "exporter's trace_id"
    root = next(s for s in exp["spans"]
                if s["name"] == "serving.migrated")
    assert root["parent_id"] == manifest["trace"]["span_id"], \
        "import root must be parented under the prefill-side span"


def test_import_out_of_slots_raises_out_of_blocks(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(26)
    prompt = rng.randint(0, cfg.vocab_size, size=(6,)).astype(np.int32)
    sess_a = _sess(tiny)
    manifest, k_bytes, v_bytes, _ = _export_one(sess_a, prompt, 8)
    sess_b = _sess(tiny, max_active=1)
    # Occupy the only slot with a long-running local request.
    sess_b.submit(prompt, 32)
    while not sess_b.engine.scheduler.running:
        sess_b._step_once()
    with pytest.raises(OutOfBlocks):
        sess_b.engine.import_migrated(manifest, k_bytes, v_bytes)
    sess_b.drain()


# ---------------------------------------------------------------------------
# router: pool placement + failover at every migration stage
# ---------------------------------------------------------------------------

def _fleet(tiny, pools, **cfg_kw):
    kv = DictKV()
    reps = [LocalDisaggReplica(f"r{i}", _sess(tiny), kv, pool=p)
            for i, p in enumerate(pools)]
    cfg_kw.setdefault("failover_grace_s", 0.05)
    cfg_kw.setdefault("max_attempts", 6)
    router = DisaggRouter(reps, kv, DisaggRouterConfig(**cfg_kw))
    return router, reps, kv


def test_router_migrates_and_matches_generate(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(31)
    prompts = [rng.randint(0, cfg.vocab_size, size=(6 + 3 * i,))
               .astype(np.int32) for i in range(3)]
    router, reps, _ = _fleet(tiny, ["prefill", "decode"])
    streamed: dict[int, list] = {}
    futs = [router.submit(p, 10, stream_cb=lambda fid, t:
                          streamed.setdefault(fid, []).append(t))
            for p in prompts]
    router.drain(timeout_s=120)
    for i, (p, f) in enumerate(zip(prompts, futs)):
        res = f.result(timeout=5)
        want = _oracle(params, cfg, p, 10)
        assert list(res.tokens) == want, (i, res.tokens, want)
        assert res.metrics["migrated"] is True, res.metrics
        assert streamed[i] == want, "streaming must be exactly-once"
    for rep in reps:
        rep.session.engine.pager.check_invariants()


def test_router_prefill_death_before_publish_replays_from_prompt(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(32)
    prompt = rng.randint(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    router, reps, kv = _fleet(
        tiny, ["prefill", "prefill", "decode"])
    fut = router.submit(prompt, 8)
    fl = next(iter(router._flights.values()))
    assert fl.state == "prefilling"
    # Kill the chosen prefill replica before it ever steps: nothing
    # durable exists, so the only correct replay point is the prompt.
    victim = fl.replica
    victim.kill()
    assert not migration_published(kv, fl.mig_id)
    router.drain(timeout_s=120)
    res = fut.result(timeout=5)
    assert list(res.tokens) == _oracle(params, cfg, prompt, 8)
    assert res.metrics["migrated"] is True
    assert router.failovers >= 1
    assert res.metrics["mig_id"].endswith(".2"), \
        "a fresh prefill attempt must use a fresh write-once mig_id"


def test_router_prefill_death_after_publish_uses_durable_point(tiny):
    """The durable-point branch: the victim published its manifest
    before dying, so the flight skips re-prefill entirely and proceeds
    straight to the decode pool with the dead replica's blocks."""
    cfg, params = tiny
    rng = np.random.RandomState(33)
    prompt = rng.randint(0, cfg.vocab_size, size=(9,)).astype(np.int32)
    router, reps, kv = _fleet(
        tiny, ["prefill", "prefill", "decode"])
    fut = router.submit(prompt, 8)
    fl = next(iter(router._flights.values()))
    victim = fl.replica
    # Drive ONLY the victim until its export is durable, then kill it
    # before the router ever reads the result.
    deadline = 120
    while not migration_published(kv, fl.mig_id):
        victim.session._step_once()
        deadline -= 1
        assert deadline > 0, "export never published"
    victim.kill()
    router.drain(timeout_s=120)
    res = fut.result(timeout=5)
    assert list(res.tokens) == _oracle(params, cfg, prompt, 8)
    assert res.metrics["migrated"] is True
    assert router.failovers >= 1
    assert res.metrics["mig_id"] == fl.mig_id and \
        res.metrics["mig_id"].endswith(".1"), \
        "the durable manifest must be reused, not re-prefilled"


def test_router_decode_death_reimports_token_identically(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(34)
    prompt = rng.randint(0, cfg.vocab_size, size=(7,)).astype(np.int32)
    router, reps, kv = _fleet(
        tiny, ["prefill", "decode", "decode"], cleanup=False)
    streamed: list[int] = []
    fut = router.submit(
        prompt, 12, stream_cb=lambda fid, t: streamed.append(t))
    fl = next(iter(router._flights.values()))
    # Pump until the decode leg has streamed a few tokens, then kill
    # the decoding replica mid-stream.
    for _ in range(10_000):
        router.pump()
        if fl.state == "decoding" and fl.delivered >= 3:
            break
    else:
        raise AssertionError(f"never reached mid-decode ({fl.state})")
    fl.replica.kill()
    router.drain(timeout_s=120)
    res = fut.result(timeout=5)
    want = _oracle(params, cfg, prompt, 12)
    assert list(res.tokens) == want, (res.tokens, want)
    assert router.failovers >= 1
    assert streamed == want, \
        f"replay must not re-deliver past the high-water mark: {streamed}"


def test_router_decode_placement_prefers_warm_prefix_cache(tiny):
    """All else equal, decode placement must pick the replica whose
    radix cache already holds the migrated prompt's prefix (the import
    attaches those blocks shared), via the side-effect-free peek()."""
    cfg, params = tiny
    rng = np.random.RandomState(36)
    prompt = rng.randint(0, cfg.vocab_size, size=(9,)).astype(np.int32)
    router, reps, _ = _fleet(tiny, ["prefill", "decode", "decode"])
    # Warm ONLY the SECOND decode replica (r2) — min() would otherwise
    # settle the tie on r1, so the prefix bonus must flip the choice.
    reps[2].session.submit(prompt, 2)
    reps[2].session.drain()
    hits = _counter_value("hvd_prefix_cache_hits_total")
    misses = _counter_value("hvd_prefix_cache_misses_total")
    assert reps[2].cached_prefix(prompt) >= 4
    assert reps[1].cached_prefix(prompt) == 0
    assert _counter_value("hvd_prefix_cache_hits_total") == hits and \
        _counter_value("hvd_prefix_cache_misses_total") == misses, \
        "the placement probe must not mutate cache counters/LRU"
    before = _counter_value("hvd_disagg_placed_total",
                            pool="decode", replica="r2")
    fut = router.submit(prompt, 8)
    router.drain(timeout_s=120)
    res = fut.result(timeout=5)
    assert list(res.tokens) == _oracle(params, cfg, prompt, 8)
    assert _counter_value("hvd_disagg_placed_total", pool="decode",
                          replica="r2") == before + 1, \
        "decode must land on the replica holding the cached prefix"


def test_router_mixed_pool_serves_both_stages(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(35)
    prompt = rng.randint(0, cfg.vocab_size, size=(6,)).astype(np.int32)
    router, reps, _ = _fleet(tiny, ["mixed"])
    fut = router.submit(prompt, 6)
    router.drain(timeout_s=120)
    res = fut.result(timeout=5)
    assert list(res.tokens) == _oracle(params, cfg, prompt, 6)
    assert res.metrics["migrated"] is True


def test_router_requires_both_pools(tiny):
    kv = DictKV()
    rep = LocalDisaggReplica("r0", _sess(tiny), kv, pool="prefill")
    with pytest.raises(ValueError, match="decode-capable"):
        DisaggRouter([rep], kv)
