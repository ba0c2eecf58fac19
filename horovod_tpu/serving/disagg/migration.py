"""KV-block export/import for cross-replica request migration.

The migration unit is the pager block, not the request tensor: a
prefill replica exports exactly the blocks its request's table spans
(``blocks_for(context_len)`` of them, per layer), and the decode
replica re-attaches them through the same refcounted
:class:`~horovod_tpu.serving.kv_pager.KVPager` machinery the radix
prefix cache uses — a cached prompt prefix on the importing side
attaches shared (no payload write), only the remainder is scattered
into fresh blocks, and the request joins the running decode batch with
zero re-prefill.  Greedy decode is deterministic, so the resumed
continuation is token-identical to an unmigrated run; the parity test
in ``tests/test_disagg.py`` asserts it against
:func:`~horovod_tpu.models.llama.generate`.

The manifest is a plain JSON-able dict (schema-versioned, geometry +
payload lengths included) so the transport layer can detect torn reads
and geometry mismatches before any pool write happens.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from ...obs import REGISTRY as _obs
from ...obs import trace as _trace
from ..kv_pager import OutOfBlocks
from ..scheduler import Request, RequestState

#: manifest wire-format version; importers reject anything else.
MANIFEST_SCHEMA = 1

_m_exports = _obs.counter(
    "hvd_disagg_exports_total", "KV-block exports by outcome", ("outcome",))
_m_imports = _obs.counter(
    "hvd_disagg_imports_total", "KV-block imports by outcome", ("outcome",))
_m_bytes = _obs.counter(
    "hvd_disagg_kv_bytes_total", "KV payload bytes exported for migration")
_m_blocks_attached = _obs.counter(
    "hvd_disagg_blocks_attached_total",
    "migrated blocks attached on import, by source",
    ("source",))          # source=payload | prefix_cache


def export_request(engine, req: Request):
    """Snapshot ``req``'s KV blocks out of ``engine``'s pool.

    Must run while the pager still holds the request's table (i.e.
    before ``scheduler.finish`` releases the blocks).  Returns
    ``(manifest, k_bytes, v_bytes)`` — the payloads are C-contiguous
    ``[L, nb, BS, *row]`` dumps of the engine's first and second pool
    (K and V; a model whose cache is one pool, the latent rows, sends
    an empty second payload), one whole block per page, so the importer
    can attach any prefix of them shared and scatter the rest.
    """
    if not req.generated:
        raise ValueError(f"request {req.req_id} has no prefill emission "
                         "yet; export runs after the first token")
    cache = engine.cache
    ctx = req.context_len
    nb = cache.blocks_for(ctx)
    blocks = engine.pager.table(req.req_id)[:nb]
    try:
        idx = np.asarray(blocks, np.int32)
        # Device-side gather of just this request's pages, then one host
        # copy — never the whole pool.
        dumps = [np.ascontiguousarray(np.asarray(pool[:, idx]))
                 for pool in engine.pools]
    except Exception:
        _m_exports.labels(outcome="error").inc()
        raise
    assert len(dumps) <= 2, "the transport carries two payloads"
    k_bytes, v_bytes = ([d.tobytes() for d in dumps] + [b""])[:2]
    manifest = {
        "schema": MANIFEST_SCHEMA,
        # Torn-read sentinel: the transport re-checks this + the payload
        # lengths after fetching, so a half-rewritten manifest can never
        # reach the pool-write path.
        "version": f"{req.req_id}.{len(req.generated)}.{ctx}",
        "prompt": [int(t) for t in req.prompt],
        "prefill_tokens": [int(t) for t in (
            req.prefill_tokens if req.prefill_tokens is not None
            else req.prompt)],
        "generated": list(req.generated),
        "max_new_tokens": int(req.max_new_tokens),
        "eos_token": (None if req.eos_token is None
                      else int(req.eos_token)),
        "context_len": int(ctx),
        "n_blocks": int(nb),
        "block_size": cache.block_size,
        "n_layers": cache.n_layers,
        "rows": [list(r) for r in cache.rows],
        "dtype": str(dumps[0].dtype),
        "k_len": len(k_bytes),
        "v_len": len(v_bytes),
        # Trace context rides the manifest so the decode-side import
        # joins the exporting request's trace instead of opening a
        # fresh orphan (sampling decided once at ingress).
        "trace": req.trace.context(),
    }
    _m_exports.labels(outcome="ok").inc()
    _m_bytes.inc(len(k_bytes) + len(v_bytes))
    return manifest, k_bytes, v_bytes


def _check_geometry(engine, manifest: dict) -> None:
    cache = engine.cache
    if manifest.get("schema") != MANIFEST_SCHEMA:
        raise ValueError(
            f"migration manifest schema {manifest.get('schema')!r} != "
            f"supported {MANIFEST_SCHEMA}")
    for field, want in (("block_size", cache.block_size),
                        ("n_layers", cache.n_layers),
                        ("rows", [list(r) for r in cache.rows])):
        if manifest.get(field) != want:
            raise ValueError(
                f"migration geometry mismatch: manifest {field}="
                f"{manifest.get(field)} but this pool has {want}")
    for field in ("k_len", "v_len", "context_len", "n_blocks"):
        if field not in manifest:
            raise ValueError(f"migration manifest missing {field}")


def import_request(engine, manifest: dict, k_bytes: bytes,
                   v_bytes: bytes, *, stream_cb=None) -> Request:
    """Attach a migrated request to ``engine`` and resume decoding.

    The longest cached prefix of the migrated prompt attaches shared
    from this replica's radix cache (those pages are never written);
    the remaining blocks come off the free list and receive the
    exported payload through the engine's compiled scatter step.  The
    returned request is RUNNING in the decode batch.  Raises
    :class:`~horovod_tpu.serving.kv_pager.OutOfBlocks` when this
    engine lacks a slot or blocks right now — callers (the router)
    retry another decode replica.
    """
    _check_geometry(engine, manifest)
    if len(k_bytes) != manifest["k_len"] or \
            len(v_bytes) != manifest["v_len"]:
        _m_imports.labels(outcome="torn").inc()
        raise ValueError(
            f"migration payload torn: got {len(k_bytes)}/{len(v_bytes)} "
            f"bytes, manifest says {manifest['k_len']}/{manifest['v_len']}")
    if not manifest["generated"]:
        raise ValueError("migration manifest has no generated tokens")

    cache = engine.cache
    ctx = int(manifest["context_len"])
    nb = int(manifest["n_blocks"])
    if nb != cache.blocks_for(ctx):
        raise ValueError(f"manifest n_blocks={nb} inconsistent with "
                         f"context_len={ctx}")
    if engine.spec is not None:
        raise NotImplementedError(
            "migrated import into a speculative-decoding engine is not "
            "supported (draft cache has no migrated state)")
    if None not in engine._slots or \
            len(engine.scheduler.running) >= engine.ecfg.max_active:
        _m_imports.labels(outcome="no_slot").inc()
        raise OutOfBlocks("no free decode slot for migrated request")

    prefill = np.asarray(manifest["prefill_tokens"], np.int32)
    # Longest-prefix attach, same machinery as local admission: matched
    # blocks are shared (refcount bump, no write), and the eviction
    # valve protects them while making room for the rest.
    cached, cached_blocks = (
        engine.prefix_cache.match(prefill)
        if engine.prefix_cache is not None else (0, []))
    need = cache.blocks_for(ctx + 1) - len(cached_blocks)
    if need > engine.pager.free_blocks and engine.prefix_cache is not None:
        engine.prefix_cache.evict(need - engine.pager.free_blocks,
                                  protect=cached_blocks)
    req_id = engine._next_id
    engine._next_id += 1
    try:
        engine.pager.allocate(req_id, ctx + 1, prefix_blocks=cached_blocks)
    except OutOfBlocks:
        _m_imports.labels(outcome="no_blocks").inc()
        raise

    jnp = engine._jnp
    table = engine.pager.table(req_id)
    ncb = len(cached_blocks)
    dtype = np.dtype(manifest["dtype"])
    if ncb < nb:
        tail_nb = nb - ncb
        L = cache.n_layers

        def tail(payload, row):
            # [L, nb, BS, *row] -> [L, 1, tail_nb*BS, *row]: the scatter
            # step's pad-and-reshape is then an exact identity, so the
            # prefill-path jit is reused unchanged.
            arr = np.frombuffer(payload, dtype).reshape(
                (L, nb, cache.block_size) + tuple(row))
            return jnp.asarray(np.ascontiguousarray(arr[:, ncb:]).reshape(
                (L, 1, tail_nb * cache.block_size) + tuple(row)))

        engine.pools = engine._scatter(
            engine.pools,
            tuple(tail(b, r) for b, r in zip((k_bytes, v_bytes), cache.rows)),
            jnp.asarray(table[ncb:nb], jnp.int32))
    _m_blocks_attached.labels(source="payload").inc(nb - ncb)
    _m_blocks_attached.labels(source="prefix_cache").inc(ncb)

    now = time.monotonic()
    req = Request(
        req_id=req_id,
        prompt=np.asarray(manifest["prompt"], np.int32),
        max_new_tokens=int(manifest["max_new_tokens"]),
        eos_token=manifest["eos_token"],
        stream_cb=stream_cb,
        state=RequestState.RUNNING,
        generated=list(manifest["generated"]),
        prefill_tokens=prefill,
        context_len=ctx,
        cached_tokens=cached,
        t_submit=now, t_admitted=now, t_enqueued=now)
    # Adopt the trace context the exporter stamped into the manifest:
    # same trace_id across the handoff, parented under the prefill-side
    # span, and its sampling decision honored.  Old manifests without
    # the field fall back to a fresh local trace.
    req.trace = _trace.TRACER.start_trace(
        "serving.migrated", lane=f"req{req_id}",
        timeline=engine.timeline, parent=manifest.get("trace"),
        req_id=req_id, migrated=True, context_len=ctx, cached_blocks=ncb)
    req.open_phase("decode", migrated=True)
    engine.scheduler.running.append(req)
    engine._assign_slot(req)
    if engine.prefix_cache is not None:
        # The migrated prompt's pages are now first-class local pages;
        # share them so future local admissions (or re-imports of the
        # same request after a decode-replica failover) prefix-attach.
        engine.prefix_cache.insert(prefill, table)
    _m_imports.labels(outcome="ok").inc()
    return req
