"""Draft-model speculative decoding as a scheduler mode.

Per round, for every running request (the whole fixed decode batch at
once):

1. **draft** — a small llama config decodes ``k`` tokens sequentially
   over its OWN page pools (same ``num_blocks``/``block_size`` geometry
   as the target, so both models index the pool through the SAME block
   tables — one allocator, two pools);
2. **verify** — ONE target forward
   (:func:`horovod_tpu.models.llama.extend_step_paged`) over the
   ``k + 1`` tokens ``[t_last, d_1..d_k]`` at positions ``C..C+k``
   yields the target's greedy token ``g_j`` after every prefix;
3. **accept** — the agreeing prefix ``d_1..d_m`` (``d_i == g_{i-1}``)
   is emitted plus the bonus token ``g_m``, so every round emits at
   least one token and the emitted stream equals target-only greedy
   decoding EXACTLY, independent of draft quality (the draft only
   decides how many target-correct tokens each round yields);
4. **roll back** — the table is truncated to the accepted context via
   :meth:`KVPager.truncate`, so rejected positions' stale K/V can
   never be read: positions inside kept blocks are overwritten by the
   next round's contiguous writes before anything attends that far, and
   whole rejected blocks go back to the free list.

The draft mirrors every context-building step of the target (prompt
prefill, prefix-hit tail prefill) into its own pools; because the
prefix cache pins block ids and a shared prefix always occupies the
same absolute positions, the draft-pool contents under pinned blocks
stay valid for every request that matches the prefix.
"""

from __future__ import annotations

import numpy as np

from ...models import llama
from ...obs import REGISTRY as _obs
from ..kv_pager import PagedKVCache
from ..scheduler import RequestState

_m_rounds = _obs.counter(
    "hvd_spec_rounds_total", "speculative draft/verify rounds executed")
_m_drafted = _obs.counter(
    "hvd_spec_tokens_drafted_total", "draft tokens proposed")
_m_accepted = _obs.counter(
    "hvd_spec_tokens_accepted_total",
    "draft tokens the target verified and accepted")
_m_accept_rate = _obs.gauge(
    "hvd_spec_accept_rate",
    "cumulative accepted/drafted ratio of this engine")


class SpecDecoder:
    """Speculative-decode engine mode: owns the draft model, its page
    pools, and the per-round draft/verify/accept/rollback loop.  Built
    by :class:`~horovod_tpu.serving.engine.ServingEngine` when
    ``EngineConfig.spec_k > 0``."""

    def __init__(self, engine, draft_params, draft_cfg: llama.LlamaConfig,
                 *, k: int) -> None:
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        if not isinstance(draft_cfg, llama.LlamaConfig):
            raise NotImplementedError(
                "the draft is a separate dense model of the Llama family "
                f"(LlamaConfig), not a {type(draft_cfg).__name__}")
        llama.check_servable(draft_cfg, None)
        if draft_cfg.vocab_size != engine.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{engine.cfg.vocab_size}: drafted ids must be target ids")
        self.eng = engine
        self.k = int(k)
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        jax, jnp = engine._jax, engine._jnp
        self._jnp = jnp
        # Same block geometry as the target pool -> shared block tables.
        self.cache = PagedKVCache(
            n_layers=draft_cfg.cache_layers,
            num_blocks=engine.cache.num_blocks,
            block_size=engine.cache.block_size,
            rows=llama.cache_rows(draft_cfg))
        # The draft pools stay replicated on a mesh: the draft is small
        # by design and its kv_heads need not divide tp.
        self.pools = tuple(jnp.zeros(shape, draft_cfg.dtype)
                           for shape in self.cache.shapes)
        self._drafted_total = 0
        self._accepted_total = 0

        from ..engine import _named
        self._prefill = jax.jit(_named(
            "hvd_serve_draft_prefill", self._prefill_impl))
        self._decode = jax.jit(_named(
            "hvd_serve_draft_decode", self._decode_impl),
            donate_argnums=(1,))
        self._extend = jax.jit(_named(
            "hvd_serve_draft_extend", self._extend_impl),
            donate_argnums=(1,))

    # -- draft-model jitted bodies (target mesh rules do not apply) ------
    def _prefill_impl(self, params, tokens, last_pos):
        _, kept, _ = llama.prefill_step(
            params, tokens, self.draft_cfg, mesh=None, last_pos=last_pos)
        return kept

    def _decode_impl(self, params, pools, tok, pos, tables):
        logits, pools, _ = llama.decode_step_paged(
            params, tok, pos, pools, tables, self.draft_cfg, mesh=None,
            use_flash=False)
        return self.eng._pick(logits), pools

    def _extend_impl(self, params, pools, tok, pos, valid, tables):
        _, pools, _ = llama.extend_step_paged(
            params, tok, pos, valid, pools, tables, self.draft_cfg,
            mesh=None)
        return pools

    # -- context mirroring ----------------------------------------------
    def mirror_prefill(self, req, padded: np.ndarray, n_tokens: int
                       ) -> None:
        """Run the draft's prompt prefill and scatter its K/V into the
        draft pools under the request's (shared) block table — the
        draft-side twin of the engine's prefill+scatter."""
        jnp = self._jnp
        eng = self.eng
        kept = self._prefill(
            self.draft_params, jnp.asarray(padded),
            np.asarray([n_tokens - 1], np.int32))
        blocks = eng.pager.table(req.req_id)
        nb = self.cache.blocks_for(n_tokens)
        self.pools = eng._scatter(
            self.pools, kept, np.asarray(blocks[:nb], np.int32))

    def mirror_extend(self, tok2, pos2, val2, tables) -> None:
        """Mirror a prefix-hit tail prefill into the draft pools (the
        cached head's draft K/V is already there from the insert-time
        request — pinned block ids are never reallocated)."""
        jnp = self._jnp
        self.pools = self._extend(
            self.draft_params, self.pools, jnp.asarray(tok2),
            jnp.asarray(pos2), jnp.asarray(val2), jnp.asarray(tables))

    # -- the round -------------------------------------------------------
    def tick(self, span) -> list:
        """One speculative round for the whole running set, under the
        engine's ``hvd.serve.decode`` profiler span; returns the
        (request, token) emissions like ``ServingEngine._decode_tick``.
        The span and the table counters read once a round, not once a
        decode call: the round's k + 1 draft steps and its verify step
        all walk the one table counted here, which already holds the
        blocks reserved for the round's k + 1 positions, and the round
        has no child spans."""
        eng = self.eng
        jnp = self._jnp
        sched = eng.scheduler
        k = self.k
        from ..kv_pager import OutOfBlocks
        from ..engine import _bucket_pow2
        # Reserve the whole round's write window (k drafts + bonus) up
        # front; rollback returns whatever goes unused.
        for req in list(sched.running):
            if req in sched.running:
                try:
                    sched.grow(req, k + 1)
                except OutOfBlocks as e:
                    sched.fail_running(req, e)
        eng._sync_slots()
        active = [r for r in eng._slots if r is not None]
        if not active:
            return []
        R = eng.ecfg.max_active
        need_cols = max(self.cache.blocks_for(r.context_len + k + 1)
                        for r in active)
        n_cols = min(_bucket_pow2(need_cols), self.cache.num_blocks)
        tok = np.zeros((R,), np.int32)
        pos = np.zeros((R,), np.int32)
        act = np.zeros((R,), bool)
        ids = [-1] * R
        for i, r in enumerate(eng._slots):
            if r is None:
                continue
            tok[i] = r.generated[-1]
            pos[i] = r.context_len
            act[i] = True
            ids[i] = r.req_id
        tables = eng.pager.table_matrix(ids, n_cols)
        eng._count_table(span, tables)
        tables = jnp.asarray(tables)

        # 1. draft k tokens sequentially with the small model.
        drafts = np.zeros((R, k), np.int32)
        cur = jnp.asarray(tok)
        pools = self.pools
        for j in range(k):
            cur, pools = self._decode(
                self.draft_params, pools, cur,
                jnp.asarray(pos + j, jnp.int32), tables)
            drafts[:, j] = np.asarray(cur)
        # Write d_k's K/V too (output discarded): a fully-accepted round
        # keeps position C+k in context, and without this write that
        # position would stay a hole the draft attends over forever.
        _, self.pools = self._decode(
            self.draft_params, pools, cur,
            jnp.asarray(pos + k, jnp.int32), tables)

        # 2. verify all k+1 positions in one target forward.
        vtok = np.concatenate([tok[:, None], drafts], axis=1)
        vpos = pos[:, None] + np.arange(k + 1, dtype=np.int32)[None, :]
        valid = np.repeat(act[:, None], k + 1, axis=1)
        out, eng.pools = eng._extend(
            eng.params, eng.pools, jnp.asarray(vtok), jnp.asarray(vpos),
            jnp.asarray(valid), tables)
        g = eng._fetch(out, span)                            # [R, k+1]

        # 3./4. accept the agreeing prefix + bonus token, roll back rest.
        _m_rounds.inc()
        emitted = []
        for i, r in enumerate(list(eng._slots)):
            if r is None:
                continue
            m = 0
            while m < k and int(drafts[i, m]) == int(g[i, m]):
                m += 1
            _m_drafted.inc(k)
            _m_accepted.inc(m)
            self._drafted_total += k
            self._accepted_total += m
            C = r.context_len
            for t in [int(drafts[i, j]) for j in range(m)] + [int(g[i, m])]:
                emitted.append((r, eng._emit(r, t)))
                if r.state is not RequestState.RUNNING:
                    break                  # eos/length: blocks released
            if r.state is RequestState.RUNNING:
                r.context_len = C + m + 1
                eng.pager.truncate(r.req_id, r.context_len)
        if self._drafted_total:
            _m_accept_rate.set(self._accepted_total / self._drafted_total)
        return emitted
