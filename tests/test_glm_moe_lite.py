"""GLM-4.7-Flash (``models/glm_moe_lite.py``) and what serving it added:
the one latent-attention mixer, the latent paged pool and its decode
kernel, the expert layer inside the serving steps, and a page pool whose
geometry comes from the model.  On the CPU, at tiny widths in float32:
one dense and two expert layers, 8 experts, 2 a token, seeded.

The reference is the benchmark's own (``chipbench/reference_glm.py``:
plain ``jax.numpy``, expanded attention, every expert on every token),
on the benchmark's seeded weights.  Logits are compared, not tokens.
Tolerances: 2e-4 on logits of unit scale is float32 round-off through
three layers summed in another order (the grouped experts, the absorbed
query, the kernel's online softmax); a planted fault reads 1e-2 and more.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_glm as ref
from chipbench import weights_glm as wts
from horovod_tpu import serving
from horovod_tpu.models import glm_moe_lite as G
from horovod_tpu.models import kimi_linear as KL
from horovod_tpu.models import layers, llama
from horovod_tpu.obs import REGISTRY
from horovod_tpu.ops import flash_attention as FA
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.moe import moe_layer_held
from horovod_tpu.serving import EngineConfig, ServingEngine
from horovod_tpu.serving.kv_pager import KVPager, OutOfBlocks, PagedKVCache
from horovod_tpu.serving.scheduler import Request, Scheduler

PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, topk_method="noaux_tc",
    norm_topk_prob=True, num_attention_heads=2, n_group=1, topk_group=1,
    n_routed_experts=8, n_shared_experts=1, routed_scaling_factor=1.8,
    num_experts_per_tok=2, first_k_dense_replace=1, num_hidden_layers=3,
    partial_rotary_factor=1, rms_norm_eps=1e-5, rope_scaling=None,
    rope_theta=10000.0, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=128)
DIMS = wts.dims_of(PUBLISHED)
CFG = G.GlmMoeLiteConfig.from_published(PUBLISHED, dtype=jnp.float32)
KEY = wts.root_key(2 ** 31 + 35)
TOL = 2e-4
ENGINE = EngineConfig(block_size=4, num_blocks=40, max_active=3,
                      use_flash="interpret", prefill_buckets=(8, 16, 24))
PROMPTS = [np.asarray(jax.random.randint(jax.random.PRNGKey(i), (n,), 0,
                                         CFG.vocab_size), np.int32)
           for i, n in enumerate((5, 9, 14, 7))]
NEW = 6


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: wts.stacked(k, DIMS, jnp.float32))(KEY)


def _reference_logits(seq):
    """The reference's logits and picks on ``seq``, padded to a length
    its query blocks take (causality keeps the tail inert)."""
    S = len(seq)
    tok = np.zeros((-(-S // 8) * 8,), np.int32)
    tok[:S] = seq
    old, ref.Q_BLOCK = ref.Q_BLOCK, 8
    try:
        logits, experts, scores = ref.forward(KEY, tok, DIMS, jnp.float32)
    finally:
        ref.Q_BLOCK = old
    return np.asarray(logits)[:S], np.asarray(experts)[:, :S], \
        np.asarray(scores)[:, :S]


# -- (1) forward against the reference ------------------------------------------

def test_config_is_the_published_one_and_the_tiny_one_matches_the_leaves():
    assert CFG == G.GlmMoeLiteConfig.tiny(vocab_size=128, rope_theta=10000.0)
    full = G.GlmMoeLiteConfig()
    assert (full.cache_values, full.cache_row, full.qk_dim) == (576, 640, 256)
    assert G.layer_runs(full) == [("dense", 1), ("moe", 46)]
    shapes = jax.eval_shape(lambda: G.init_params(CFG, jax.random.PRNGKey(0)))
    made = jax.eval_shape(lambda: wts.stacked(KEY, DIMS, jnp.float32))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), shapes) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), made)
    with pytest.raises(NotImplementedError, match="n_group"):
        G.GlmMoeLiteConfig.from_published(dict(PUBLISHED, n_group=2))


def test_forward_matches_the_reference_logits_and_picks(params):
    seq = PROMPTS[2]
    logits, stats = G.forward(params, jnp.asarray(seq)[None], CFG, picks=True)
    want, experts, scores = _reference_logits(seq)
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=TOL)
    assert stats["experts"].shape == (2, len(seq), 2)
    got = ref.compare_picks(experts, scores, np.asarray(stats["experts"]),
                            len(seq))
    assert got["agree_share"] == 1.0 and got["clean_flips"] == 0
    assert got["clean_places"] == 2 * len(seq)
    np.testing.assert_array_equal(
        np.asarray(stats["expert_counts"]).sum(-1), [2 * len(seq)] * 2)


def test_compare_picks_judges_clean_places_only():
    """A flip at layer 0, token 3 leaves layer 1 judged on tokens 0 to 2
    alone; a flip there with a wide margin is a fault, one past it is
    not judged."""
    S, E = 6, 4
    scores = np.tile(np.asarray([0.9, 0.8, 0.5, 0.1]), (2, S, 1))
    scores[0, 3] = [0.9, 0.8, 0.7999, 0.1]
    refp = np.tile(np.asarray([0, 1]), (2, S, 1))
    prog = refp.copy()
    prog[0, 3] = [0, 2]                     # near tie, clean
    prog[1, 5] = [0, 3]                     # after a flip: not judged
    got = ref.compare_picks(refp, scores, prog, S)
    assert got["clean_flips"] == 1 and got["clean_places"] == S + 3
    assert got["clean_flip_margin"] == pytest.approx(1e-4)
    assert got["agree_share"] == pytest.approx(10 / 12)
    prog[1, 1] = [0, 3]                     # clean, and no near tie
    assert ref.compare_picks(refp, scores, prog, S)["clean_flip_margin"] \
        == pytest.approx(0.7)


# -- (2) prefill then decode through the engine ------------------------------------

def _serve(params, engine_cfg=ENGINE):
    """The prompts through a ``ServingEngine`` whose prefill and decode
    programs hand back their logits: ``(engine, requests, served)`` with
    ``served[req_id][position]`` the logits the token after ``position``
    was picked from."""
    eng = ServingEngine(params, CFG, engine_cfg=engine_cfg)
    served: dict = {}
    prefill = jax.jit(lambda p, tok, last: llama.prefill_step(
        p, tok, CFG, last_pos=last))
    decode = jax.jit(lambda p, pools, tok, pos, tables:
                     llama.decode_step_paged(
                         p, tok, pos, pools, tables, CFG,
                         use_flash=eng._use_flash, interpret=eng._interpret))

    def spy_prefill(p, tokens, last_pos):
        logits, kept, stats = prefill(p, tokens, last_pos)
        req = next(r for r in eng._slots if r is not None and np.array_equal(
            r.prefill_tokens, np.asarray(tokens)[0, :int(last_pos[0]) + 1]))
        served.setdefault(req.req_id, {})[int(last_pos[0])] = \
            np.asarray(logits[0])
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), kept

    def spy_decode(p, pools, tok, pos, tables):
        logits, pools, stats = decode(p, pools, tok, pos, tables)
        for i, r in enumerate(eng._slots):
            if r is not None:
                served[r.req_id][int(pos[i])] = np.asarray(logits[i])
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), pools

    eng._prefill, eng._decode = spy_prefill, spy_decode
    reqs = [eng.submit(p, NEW) for p in PROMPTS]
    eng.run()
    return eng, reqs, served


@pytest.mark.parametrize("use_flash", ["interpret", "never"])
def test_served_logits_match_the_reference(params, use_flash):
    """Four prompts over three slots: bucketed prefill through the
    expanded form, the rows scattered into the latent pool, decode ticks
    through the absorbed form (the kernel interpreted, or the gather)."""
    import dataclasses
    eng, reqs, served = _serve(params, dataclasses.replace(
        ENGINE, use_flash=use_flash))
    assert eng.attention_path == {"interpret": "pallas-mla-interpret",
                                  "never": "gather"}[use_flash]
    assert len(eng.pools) == 1 and eng.pools[0].shape == (3, 40, 4, 128)
    for r in reqs:
        assert len(r.generated) == NEW
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        want, _, _ = _reference_logits(seq)
        P = len(r.prompt)
        for j in range(NEW):
            np.testing.assert_allclose(served[r.req_id][P - 1 + j],
                                       want[P - 1 + j], atol=TOL)
    eng.pager.check_invariants()


def test_a_planted_fault_is_over_the_tolerance(params, monkeypatch):
    """The rope key left unrotated in the cache row reads far over TOL."""
    real = layers.mla_row
    monkeypatch.setattr(G, "mla_row", lambda c, k_pe, width: real(
        c, jnp.zeros_like(k_pe), width))
    _, reqs, served = _serve(params)
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        want, _, _ = _reference_logits(seq)
        P = len(r.prompt)
        worst = max(worst, max(
            float(np.abs(served[r.req_id][P - 1 + j] - want[P - 1 + j]).max())
            for j in range(1, NEW)))
    assert worst > 1e-2


# -- (3) absorbed equals expanded ------------------------------------------------

def test_absorbed_attention_equals_expanded_in_float32():
    B, T, H, C, nope, R, Dv, W = 2, 12, 3, 32, 16, 8, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, 1, H, nope + R))
    c = jax.random.normal(ks[1], (B, T, C))
    k_pe = jax.random.normal(ks[2], (B, T, R))
    w_kvb = jax.random.normal(ks[3], (C, H, nope + Dv)) / np.sqrt(C)
    scale = (nope + R) ** -0.5
    mask = (jnp.arange(T)[None, :] < jnp.asarray([T, 7])[:, None])[:, None]
    k, v = layers.mla_expand(c, k_pe, w_kvb, nope)
    want = layers.cached_attend(q, k, v, mask, scale)
    rows = layers.mla_row(c, k_pe, W)[:, :, None]
    assert rows.shape == (B, T, 1, W)
    assert not np.asarray(rows[..., C + R:]).any()
    o = layers.cached_attend(layers.mla_absorb(q, w_kvb, nope, W), rows,
                             rows[..., :C], mask, scale)
    got = jnp.einsum("bshc,chv->bshv", o, w_kvb[..., nope:])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- (4) the kernel against the gather -------------------------------------------

KERNEL_CASES = {
    # lengths, table columns, block size
    "row-of-length-0": ([9, 0, 17], 5, 4),
    "last-group-of-one-page": ([33, 36, 1], 9, 4),      # groups of 8 pages
    "table-wider-than-the-stream": ([5, 3, 2], 16, 4),
    "whole-groups": ([32, 64, 16], 16, 4),
    "all-rows-idle-but-the-last": ([0, 0, 11], 4, 4),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_mla_kernel_matches_the_gather(case, monkeypatch):
    lengths, n_cols, BS = KERNEL_CASES[case]
    monkeypatch.setattr(FA, "_MLA_GROUP_TOKENS", 32)     # 8 pages a group
    B, H, W, C, L, li = len(lengths), 3, 128, 32, 3, 2
    NB = 1 + B * n_cols
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    q = jax.random.normal(ks[0], (B, H, W))
    pool = jax.random.normal(ks[1], (L, NB, BS, W))
    # scratch block 0 holds NaN: nothing of it may be read
    pool = pool.at[:, 0].set(jnp.nan)
    tables = np.zeros((B, n_cols), np.int32)
    for b, n in enumerate(lengths):
        live = -(-n // BS)
        tables[b, :live] = 1 + b * n_cols + np.arange(live)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    got = FA.mla_paged_attention(q, pool, li, tables, lengths, v_dim=C,
                                 scale=0.2, interpret=True)
    assert got.shape == (B, H, C) and np.isfinite(np.asarray(got)).all()
    rows = layers.gather_blocks(jnp.nan_to_num(pool[li]), tables)[:, :, None]
    mask = (jnp.arange(n_cols * BS)[None, :] < lengths[:, None])[:, None]
    want = layers.cached_attend(q[:, None], rows, rows[..., :C], mask,
                                0.2)[:, 0]
    want = jnp.where((lengths > 0)[:, None, None], want, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_mla_kernel_geometries_and_group_pages():
    ok = FA.mla_paged_supported
    assert ok(32, 640, 20, 2) and ok(16, 640, 20, 2) and ok(8, 128, 4, 4)
    assert not ok(32, 576, 20, 2)          # not whole lanes
    assert not ok(8, 640, 20, 2)           # half a sublane tile of bf16
    assert FA.mla_group_pages(32, 640, 32, 2, 512) == 16     # 512 tokens
    assert FA.mla_group_pages(32, 640, 32, 2, 6) == 4        # a power of two
    assert G.paged_kernel_ok(G.GlmMoeLiteConfig(), None, 32)
    assert not G.paged_kernel_ok(G.GlmMoeLiteConfig(), None, 8)
    assert G.paged_kernel_ok(CFG, None, 3, interpret=True)


# -- (5) pager, scheduler and the pool's geometry ---------------------------------

def test_pool_geometry_comes_from_the_model():
    full = G.GlmMoeLiteConfig(n_layers=7)
    cache = PagedKVCache(n_layers=full.cache_layers, num_blocks=18432,
                         block_size=32, rows=G.cache_rows(full))
    assert cache.shapes == ((7, 18432, 32, 640),)
    # what the configuration file states: 8,960 B a token as the pool pads
    # it (8,064 as published), 286,720 B a block
    assert cache.bytes_per_block(2) == 286_720 == 32 * 8960
    assert full.n_layers * full.cache_values * 2 == 8064
    gqa = llama.LlamaConfig.tiny()
    two = PagedKVCache(n_layers=2, num_blocks=8, block_size=4,
                       rows=llama.cache_rows(gqa))
    assert two.shapes == ((2, 8, 4, 2, 16),) * 2
    assert two.bytes_per_block(4) == 2 * 2 * 4 * 2 * 16 * 4


def test_engine_gauges_read_the_latent_geometry(params):
    ServingEngine(params, CFG, engine_cfg=ENGINE)
    assert REGISTRY.get("hvd_serving_cache_layers").value == 3
    assert REGISTRY.get("hvd_serving_kv_bytes_per_token").value == \
        3 * 128 * 4


def test_preempt_and_resume_on_the_latent_pool(params):
    """A pool of 12 usable blocks under three streams of 20 and more
    tokens: requests are preempted and prefilled again, and still say
    what an unpressed engine says."""
    import dataclasses
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, CFG.vocab_size, size=n).astype(np.int32)
               for n in (13, 11, 14, 9)]
    outs = []
    for blocks in (40, 13):
        eng = ServingEngine(params, CFG, engine_cfg=dataclasses.replace(
            ENGINE, num_blocks=blocks, prefill_buckets=(8, 16, 24, 32)))
        before = REGISTRY.get("hvd_serving_preemptions_total").value
        reqs = [eng.submit(p, 10) for p in prompts]
        eng.run()
        outs.append([r.generated for r in reqs])
        eng.pager.check_invariants()
        assert eng.pager.free_blocks == blocks - 1
        preempted = REGISTRY.get("hvd_serving_preemptions_total").value \
            - before
        assert (preempted > 0) == (blocks == 13)
    assert outs[0] == outs[1]


def test_scheduler_admits_by_blocks_of_the_latent_pool():
    pager = KVPager(PagedKVCache(n_layers=3, num_blocks=6, block_size=4,
                                 rows=((128,),)))
    sched = Scheduler(pager, max_active=4, prefill_token_budget=64)
    for i, n in enumerate((7, 9, 5)):
        sched.submit(Request(req_id=i, prompt=np.zeros(n, np.int32),
                             max_new_tokens=4))
    admitted = sched.admit()
    assert [r.req_id for r in admitted] == [0, 1]    # 2 + 3 blocks of 5 free
    pager.check_invariants()
    with pytest.raises(OutOfBlocks):
        pager.allocate(9, 40)


# -- (6) prefix cache, migration, speculative verify, by pool geometry -------------

@pytest.fixture(scope="module")
def families(params):
    gqa = llama.LlamaConfig.tiny(vocab_size=CFG.vocab_size)
    return {"gqa": (gqa, llama.init_params(gqa, jax.random.PRNGKey(1))),
            "latent": (CFG, params)}


def _plain_tokens(cfg, p, prompt, n):
    """What an engine with no front door says: the oracle of the three."""
    sess = serving.serve(p, cfg, num_blocks=64, block_size=8, max_active=4,
                         use_flash="never")
    fut = sess.submit(prompt, n)
    sess.drain()
    sess.close()
    return list(fut.result().tokens)


@pytest.mark.parametrize("family", ["gqa", "latent"])
def test_prefix_hit_tail_prefill_by_geometry(families, family):
    cfg, p = families[family]
    sess = serving.serve(p, cfg, num_blocks=64, block_size=8, max_active=4,
                         use_flash="never", prefix_cache=True)
    rng = np.random.RandomState(3)
    head = rng.randint(0, cfg.vocab_size, size=(24,)).astype(np.int32)
    tail = rng.randint(0, cfg.vocab_size, size=(7,)).astype(np.int32)
    first = sess.submit(head, 8)
    sess.drain()
    second = sess.submit(np.concatenate([head, tail]), 8)
    sess.drain()
    assert second.result().metrics["cached_tokens"] == 24
    assert first.result().metrics["cached_tokens"] == 0
    assert list(second.result().tokens) == _plain_tokens(
        cfg, p, np.concatenate([head, tail]), 8)
    sess.engine.pager.check_invariants()
    sess.close()


@pytest.mark.parametrize("family", ["gqa", "latent"])
def test_migrated_request_continues_by_geometry(families, family):
    cfg, p = families[family]
    kw = dict(num_blocks=32, block_size=4, max_active=4, use_flash="never")
    a, b = serving.serve(p, cfg, **kw), serving.serve(p, cfg, **kw)
    prompt = np.random.RandomState(21).randint(
        0, cfg.vocab_size, size=(9,)).astype(np.int32)
    box = {}
    fut = a.submit(prompt, 10, migrate_cb=lambda *mig: box.update(mig=mig))
    a.drain()
    assert fut.result(timeout=5).metrics["finish_reason"] == "migrated"
    manifest, k_bytes, v_bytes = box["mig"]
    rows = [list(r) for r in a.engine.cache.rows]
    assert manifest["rows"] == rows
    per_block = [cfg.cache_layers * 4 * int(np.prod(r)) * 4 for r in rows]
    assert [len(k_bytes), len(v_bytes)] == \
        ([3 * n for n in per_block] + [0])[:2]       # 9 tokens: 3 blocks
    got = b.import_migrated(manifest, k_bytes, v_bytes)
    b.drain()
    assert list(got.result(timeout=5).tokens) == _plain_tokens(
        cfg, p, prompt, 10)
    with pytest.raises(ValueError, match="geometry"):
        b.import_migrated(dict(manifest, rows=[[1, 2]]), k_bytes, v_bytes)
    for s in (a, b):
        s.engine.pager.check_invariants()
        s.close()


@pytest.mark.parametrize("family", ["gqa", "latent"])
def test_speculative_verify_by_geometry(families, family):
    """The target of either geometry behind a dense draft of the same
    vocabulary: the emitted tokens are the target's own."""
    cfg, p = families[family]
    draft_cfg, draft = families["gqa"]
    sess = serving.serve(p, cfg, num_blocks=64, block_size=8, max_active=4,
                         use_flash="never", spec_k=2, draft_params=draft,
                         draft_cfg=draft_cfg)
    prompts = [np.random.RandomState(4 + i).randint(
        0, cfg.vocab_size, size=(n,)).astype(np.int32)
        for i, n in enumerate((5, 9))]
    futs = [sess.submit(q, 9) for q in prompts]
    sess.drain()
    for q, f in zip(prompts, futs):
        assert list(f.result().tokens) == _plain_tokens(cfg, p, q, 9)
    assert sess.engine.spec._drafted_total > 0
    assert len(sess.engine.spec.pools) == 2          # the draft's own K, V
    sess.engine.pager.check_invariants()
    sess.close()


def test_the_draft_is_a_dense_model_of_the_llama_family(families):
    cfg, p = families["latent"]
    with pytest.raises(NotImplementedError, match="draft"):
        serving.serve(p, cfg, num_blocks=16, block_size=4, max_active=2,
                      use_flash="never", spec_k=2, draft_params=p,
                      draft_cfg=cfg)


# -- (7) one mixer for two models ---------------------------------------------------

def _kimi_mla_mixer_as_it_stood(x, lp, cfg, mesh):
    """``kimi_linear._mla_mixer`` of PR 34, verbatim."""
    B, S, _ = x.shape
    C, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
    kva = jnp.einsum("bsd,dc->bsc", x, lp["w_kva"])
    c = layers.rmsnorm(kva[..., :C], lp["kv_norm"], cfg.rms_eps)
    kv = jnp.einsum("bsc,chk->bshk", c, lp["w_kvb"])
    k_pe = jnp.broadcast_to(kva[:, :, None, C:],
                            (B, S, cfg.n_heads, cfg.qk_rope_dim))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    o = layers.attention(q, k, kv[..., nope:], mesh, True)
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), None


@pytest.mark.parametrize("path", ["dense", "flash"])
def test_the_one_mixer_gives_kimi_what_its_own_gave(path, monkeypatch):
    monkeypatch.setattr(layers, "_FORCE_FLASH_INTERPRET", path == "flash")
    kcfg = KL.KimiLinearConfig.tiny()
    shapes = KL.leaf_shapes(kcfg, "mla_moe")
    ks = jax.random.split(jax.random.PRNGKey(7), len(shapes) + 1)
    lp = {n: jax.random.normal(k, s) / np.sqrt(s[0])
          for k, (n, (s, _)) in zip(ks, sorted(shapes.items()))}
    x = jax.random.normal(ks[-1], (2, 128, kcfg.d_model))
    want, _ = _kimi_mla_mixer_as_it_stood(x, lp, kcfg, None)
    got, kept = KL.layer_pair("mla_moe", kcfg, None)[0](x, lp)
    assert kept is None
    assert np.array_equal(np.asarray(got), np.asarray(want))     # bitwise
    # and its gradient, which the trainer takes
    g = lambda f: jax.grad(lambda x: jnp.sum(f(x)[0] ** 2))(x)
    assert np.array_equal(
        np.asarray(g(lambda x: _kimi_mla_mixer_as_it_stood(x, lp, kcfg,
                                                           None))),
        np.asarray(g(lambda x: KL.layer_pair("mla_moe", kcfg, None)[0](
            x, lp))))


# -- (8) the expert layer at a tick's rows and at a prompt's --------------------------

@pytest.mark.parametrize("rows", [64, 4096])
def test_expert_layer_equals_the_per_token_sum(rows):
    D, E, F, k = 32, 8, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(rows), 8)
    x = jax.random.normal(ks[0], (rows, D))
    router = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    experts = {"gate": jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
               "up": jax.random.normal(ks[3], (E, D, F)) / np.sqrt(D),
               "down": jax.random.normal(ks[4], (E, F, D)) / np.sqrt(F)}
    shared = {"w_gate": jax.random.normal(ks[5], (D, F)) / np.sqrt(D),
              "w_up": jax.random.normal(ks[6], (D, F)) / np.sqrt(D),
              "w_down": jax.random.normal(ks[7], (F, D)) / np.sqrt(F)}
    tile = G.moe_tile(rows)
    assert tile == {64: 64, 4096: 256}[rows]
    out, stats = moe_layer_held(x, router, jnp.zeros((E,)), experts, (0, E),
                                shared, k=k, scale=1.8, tile=tile, picks=True)
    w = {"router": router, "router_bias": jnp.zeros((E,)),
         "e_gate": experts["gate"], "e_up": experts["up"],
         "e_down": experts["down"], "s_gate": shared["w_gate"],
         "s_up": shared["w_up"], "s_down": shared["w_down"]}
    dims = dict(n_experts=E, experts_per_token=k, renormalize=True,
                routed_scale=1.8)
    want, picked, _ = ref.expert_mlp(w, x, dims)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(stats["experts"]), -1),
                                  np.sort(np.asarray(picked), -1))
    assert int(stats["expert_counts"].sum()) == rows * k       # dropless
    # a stack of two layers' experts, this layer's from row E on
    stack = {n: jnp.concatenate([jnp.zeros_like(a), a]) for n, a in
             experts.items()}
    again, _ = moe_layer_held(x, router, jnp.zeros((E,)), stack, (0, E),
                              shared, k=k, scale=1.8, tile=tile,
                              first_row=jnp.asarray(E))
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


def _reference_share(x, router, bias, experts, held, k, scale):
    """The benchmark's reference layer (float32, every expert on every
    token) with the experts held elsewhere, and the shared one, zero."""
    D, E = router.shape
    F = experts["gate"].shape[-1]
    full = lambda a: jnp.zeros((E,) + a.shape[1:]).at[held[0]:held[1]].set(
        a.astype(jnp.float32))
    w = {"router": router, "router_bias": bias, "e_gate": full(
        experts["gate"]), "e_up": full(experts["up"]), "e_down": full(
        experts["down"]), "s_gate": jnp.zeros((D, F)),
        "s_up": jnp.zeros((D, F)), "s_down": jnp.zeros((F, D))}
    return ref.expert_mlp(w, x.astype(jnp.float32), dict(
        n_experts=E, experts_per_token=k, renormalize=True,
        routed_scale=scale))[0]


# rows, tile, experts held of the 8, an expert no token picks, a stack
_COMBINE_CASES = {
    "tile-16": (40, 16, (0, 8), None, False),
    "tile-256-a-prompt": (1000, 256, (0, 8), None, False),
    "a-pair-held-elsewhere": (40, 16, (2, 6), None, False),
    "an-expert-with-no-token": (40, 16, (0, 8), 3, False),
    "first-row-into-a-stack": (40, 16, (0, 8), None, True),
    "one-tile-an-expert-a-tick": (64, 64, (0, 8), None, False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", _COMBINE_CASES)
def test_the_list_form_is_the_add_form_and_the_per_token_sum(
        case, dtype, monkeypatch):
    """The two ways the grouped products' results get back to the rows
    (``moe.combine_form``) against each other and against the plain sum,
    outputs and gradients: every case has padding rows (no expert's run
    is whole tiles), and each adds what its name says."""
    rows, tile, held, starved, stacked = _COMBINE_CASES[case]
    D, E, F, k = 32, 8, 16, 2
    n = held[1] - held[0]
    ks = jax.random.split(jax.random.PRNGKey(rows + tile), 6)
    x = jax.random.normal(ks[0], (rows, D)).astype(dtype)
    router = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    bias = jnp.zeros((E,))
    if starved is not None:
        bias = bias.at[starved].set(-10.0)
    experts = {
        "gate": (jax.random.normal(ks[2], (n, D, F)) / np.sqrt(D)),
        "up": (jax.random.normal(ks[3], (n, D, F)) / np.sqrt(D)),
        "down": (jax.random.normal(ks[4], (n, F, D)) / np.sqrt(F))}
    experts = jax.tree.map(lambda a: a.astype(dtype), experts)
    cot = jax.random.normal(ks[5], (rows, D)).astype(dtype)
    first_row = jnp.asarray(n) if stacked else None

    def layer(form):
        def f(x, router, experts):
            held_experts = jax.tree.map(
                lambda a: jnp.concatenate([jnp.zeros_like(a), a]),
                experts) if stacked else experts
            out, stats = moe_layer_held(
                x, router, bias, held_experts, held, None, k=k, scale=1.8,
                tile=tile, first_row=first_row)
            return out, stats
        monkeypatch.setattr(moe, "combine_form", lambda *a: form)
        (out, stats), vjp = jax.vjp(f, x, router, experts)
        return out, stats, vjp((cot, jax.tree.map(jnp.zeros_like, stats)))

    out_list, stats, g_list = layer("list")
    out_add, _, g_add = layer("add")
    want, vjp = jax.vjp(lambda *a: _reference_share(
        a[0], a[1], bias, a[2], held, k, 1.8), x, router, experts)
    g_want = vjp(cot.astype(jnp.float32))
    counts = np.asarray(stats["expert_counts"])
    assert (counts % tile).any()                          # padding rows
    assert (int(counts.sum()) < rows * k) == (n < E)      # held elsewhere
    if starved is not None:
        assert counts[starved] == 0
    # a bf16 step at the outputs' size; float32 to its own round-off
    step = 2.0 ** -8 * float(jnp.max(jnp.abs(want))) \
        if dtype == jnp.bfloat16 else 2e-5
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(out_list), f32(out_add), atol=step)
    np.testing.assert_allclose(f32(out_list), f32(want), atol=2 * step)
    # the backward is the one function, on the same residuals
    for a, b in zip(jax.tree.leaves(g_list), jax.tree.leaves(g_add)):
        np.testing.assert_array_equal(f32(a), f32(b))
    for a, b in zip(jax.tree.leaves(g_list), jax.tree.leaves(g_want)):
        scale = float(np.max(np.abs(f32(b)))) + 1e-6
        np.testing.assert_allclose(
            f32(a), f32(b), atol=scale * (2.0 ** -5 if dtype == jnp.bfloat16
                                          else 1e-4))


def test_the_form_follows_what_the_caller_holds():
    assert moe.combine_form(64, 64) == "list"
    assert moe.combine_form(16, 256) == "add"


def _eqns(jaxpr, in_loop=False):
    """Every equation under ``jaxpr`` with whether a ``while`` encloses
    it."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inner = in_loop or eqn.primitive.name == "while"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inner)


def combine_ops(fn, *args, width):
    """(list writes, row adds) in the loops of ``fn``'s jaxpr: the
    ``dynamic_update_slice`` of ``width``-wide rows and the
    ``scatter-add`` whose update is ``width`` wide."""
    writes = adds = 0
    for eqn, in_loop in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if not in_loop:
            continue
        if eqn.primitive.name == "dynamic_update_slice":
            upd = eqn.invars[1].aval
            writes += upd.ndim == 2 and upd.shape[1] == width
        if eqn.primitive.name == "scatter-add":
            upd = eqn.invars[2].aval
            adds += upd.ndim == 2 and upd.shape[1] == width
    return writes, adds


def test_the_prefill_steps_tile_loop_writes_a_list_and_adds_no_rows(params):
    """A count, not a time: the prefill program's loop over tiles puts a
    tile's rows into the list and holds no scatter-add of rows
    ``d_model`` wide; the tick's and the extend step's neither."""
    tok = jnp.asarray(PROMPTS[2])[None]
    assert combine_ops(lambda p, t: llama.prefill_step(p, t, CFG)[0],
                       params, tok, width=CFG.d_model) == (1, 0)
    pool = jnp.zeros((3, 8, 4, 128))
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    assert combine_ops(
        lambda p, pool: llama.decode_step_paged(
            p, jnp.asarray([9, 7, 0]), jnp.asarray([9, 5, 0]), (pool,),
            tables, CFG)[0], params, pool, width=CFG.d_model) == (1, 0)
    assert combine_ops(
        lambda p, pool: llama.extend_step_paged(
            p, jnp.zeros((3, 2), jnp.int32), jnp.zeros((3, 2), jnp.int32),
            jnp.ones((3, 2), bool), (pool,), tables, CFG)[0],
        params, pool, width=CFG.d_model) == (1, 0)


def _grouped_experts_as_it_stood(tokens, weights, place, tile_expert, n_tiles,
                                 experts, pair_slot, tile):
    """``moe._grouped_experts`` before it had a list form (its forward;
    the backward rule was and is ``moe._grouped_bwd``)."""
    assert pair_slot is None
    rows, _, row_w = moe._padded_lists(place, weights)

    def body(i, out):
        idx, x, wt, w = moe._tile_operands(i, tile, tokens, rows, row_w,
                                           tile_expert, experts)
        y = moe._swiglu_tile(x, w["gate"], w["up"], w["down"], wt)
        return out.at[idx].add(y, mode="drop", unique_indices=True)
    return jax.lax.fori_loop(0, n_tiles, body, jnp.zeros_like(tokens))


def test_a_share_of_the_experts_still_adds_its_rows_as_it_did(monkeypatch):
    """The trainer's layer holds a share of the experts its router scores
    (Kimi-Linear: 16 of 256; here 4 of 16): its loop keeps the add of
    rows ``d_model`` wide, no list, and gives bitwise what it gave."""
    kcfg = KL.KimiLinearConfig.tiny()
    D, E, F, k = kcfg.d_model, kcfg.n_experts, kcfg.moe_d_ff, 2
    ks = jax.random.split(jax.random.PRNGKey(36), 8)
    x = jax.random.normal(ks[0], (96, D))
    router = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    bias = 0.1 * jax.random.normal(ks[2], (E,))
    experts = {"gate": jax.random.normal(ks[3], (4, D, F)) / np.sqrt(D),
               "up": jax.random.normal(ks[4], (4, D, F)) / np.sqrt(D),
               "down": jax.random.normal(ks[5], (4, F, D)) / np.sqrt(F)}
    layer = lambda x, experts: moe_layer_held(
        x, router, bias, experts, (4, 8), None, k=k, scale=2.446, tile=8)[0]
    assert combine_ops(layer, x, experts, width=D) == (0, 1)
    got = layer(x, experts)
    assert float(jnp.max(jnp.abs(got))) > 0
    monkeypatch.setattr(moe, "_grouped_experts", _grouped_experts_as_it_stood)
    assert np.array_equal(np.asarray(got), np.asarray(layer(x, experts)))


# -- routing as it stood before it became dense work (PR 38) ----------------------------
# ``lax.top_k`` (a sort of every token's scores), ``argsort``, a histogram
# by scatter-add, the padded ``rows`` and ``pair_w`` lists filled by two
# scatters and ``pair_slot`` by a third: the reference that
# ``moe.moe_layer_held`` has to equal bit for bit.  A tile's operands and
# its products are the program's own, which read the lists as they did.

def _topk_route_as_it_stood(scores, bias, k, *, renormalize=True, scale=1.0):
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _grouped_as_it_stood(tokens, rows, weights, tile_expert, n_tiles, experts,
                         pair_slot, tile):
    def body(i, acc):
        idx, x, wt, w = moe._tile_operands(i, tile, tokens, rows, weights,
                                           tile_expert, experts)
        y = moe._swiglu_tile(x, w["gate"], w["up"], w["down"], wt)
        if pair_slot is None:
            return acc.at[idx].add(y, mode="drop", unique_indices=True)
        return jax.lax.dynamic_update_slice(
            acc, jax.lax.optimization_barrier(y), (i * tile, 0))
    if pair_slot is None:
        return jax.lax.fori_loop(0, n_tiles, body, jnp.zeros_like(tokens))
    M, D = rows.shape[0], tokens.shape[1]
    lst = jax.lax.dynamic_update_slice(
        jax.lax.empty((M, D), tokens.dtype),
        jnp.zeros((tile, D), tokens.dtype), (M - tile, 0))
    lst = jax.lax.fori_loop(0, n_tiles, body, lst)
    return sum(
        lst.at[pair_slot[:, j]].get(mode="promise_in_bounds").astype(
            jnp.float32)
        for j in range(pair_slot.shape[1])).astype(tokens.dtype)


def _grouped_fwd_as_it_stood(tokens, rows, weights, tile_expert, n_tiles,
                             experts, pair_slot, tile):
    out = _grouped_as_it_stood(tokens, rows, weights, tile_expert, n_tiles,
                               experts, pair_slot, tile)
    return out, (tokens, rows, weights, tile_expert, n_tiles, experts)


def _grouped_bwd_as_it_stood(tile, res, d_out):
    tokens, rows, weights, tile_expert, n_tiles, experts = res

    def body(i, carry):
        d_tok, d_wt, d_exp = carry
        idx, x, wt, w = moe._tile_operands(i, tile, tokens, rows, weights,
                                           tile_expert, experts)
        dy = jnp.take(d_out, idx, axis=0, mode="fill", fill_value=0)
        _, vjp = jax.vjp(moe._swiglu_tile, x, w["gate"], w["up"], w["down"],
                         wt)
        dx, dg, du, dd, dwt = vjp(dy)
        d_tok = d_tok.at[idx].add(dx, mode="drop", unique_indices=True)
        d_wt = jax.lax.dynamic_update_slice(d_wt, dwt.astype(d_wt.dtype),
                                            (i * tile,))
        e = tile_expert[i]
        d_exp = {k: d_exp[k].at[e].add(d.astype(d_exp[k].dtype))
                 for k, d in (("gate", dg), ("up", du), ("down", dd))}
        return d_tok, d_wt, d_exp

    zeros = (jnp.zeros_like(tokens), jnp.zeros_like(weights),
             jax.tree.map(jnp.zeros_like, experts))
    d_tok, d_wt, d_exp = jax.lax.fori_loop(0, n_tiles, body, zeros)
    return d_tok, None, d_wt, None, None, d_exp, None


_grouped_as_it_stood.defvjp(_grouped_fwd_as_it_stood,
                            _grouped_bwd_as_it_stood)


def _moe_layer_held_as_it_stood(tokens, router, bias, experts_held,
                                held_range, shared=None, *, k,
                                renormalize=True, scale=1.0, tile=512,
                                picks=False, first_row=None):
    T, D = tokens.shape
    first, last = held_range
    E_held = last - first
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    experts, weights = _topk_route_as_it_stood(
        scores, bias, k, renormalize=renormalize, scale=scale)
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < E_held)
    key = jnp.where(held, local, E_held)
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((E_held + 1,), jnp.int32).at[key].add(1)[:E_held]
    padded = -(-counts // tile) * tile
    starts = jnp.cumsum(padded) - padded
    begins = jnp.cumsum(counts) - counts
    M = -(-T * min(k, E_held) // tile) * tile + E_held * tile
    sorted_key = key[order]
    rank = jnp.arange(order.shape[0]) - begins[jnp.minimum(sorted_key,
                                                           E_held - 1)]
    slot = jnp.where(sorted_key < E_held,
                     starts[jnp.minimum(sorted_key, E_held - 1)] + rank, M)
    rows = jnp.full((M,), T, jnp.int32).at[slot].set(
        (order // k).astype(jnp.int32), mode="drop")
    pair_w = jnp.zeros((M,), weights.dtype).at[slot].set(
        weights.reshape(-1)[order], mode="drop")
    n_tiles = jnp.sum(padded) // tile
    tile_expert = jnp.clip(jnp.searchsorted(
        jnp.cumsum(padded), jnp.arange(M // tile) * tile, side="right"),
        0, E_held - 1).astype(jnp.int32)
    if first_row is not None:
        tile_expert = tile_expert + first_row
    pair_slot = None
    if moe.combine_form(E_held, router.shape[1]) == "list":
        pair_slot = jnp.zeros_like(order).at[order].set(
            jnp.minimum(slot, M - 1)).reshape(T, k)
    out = _grouped_as_it_stood(tokens, rows, pair_w, tile_expert, n_tiles,
                               experts_held, pair_slot, tile)
    if shared is not None:
        hidden = jax.nn.silu(tokens @ shared["w_gate"]) * \
            (tokens @ shared["w_up"])
        out = out + hidden @ shared["w_down"]
    stats = {"pairs_held": jnp.sum(counts), "expert_counts": counts}
    if picks:
        stats.update(experts=experts, scores=scores)
    return out, stats


def _planted_runs(T, D, tile, key):
    """Tokens, a router over 8 experts and a bias under which the first
    ``tile`` tokens take experts 0 and 1, the next ``tile + 1`` experts 2
    and 3, the rest 5 and 6, and no one takes 4 or 7."""
    group = np.repeat(np.arange(3), [tile, tile + 1, T - 2 * tile - 1])
    x = 0.1 * jax.random.normal(key, (T, D)) + \
        2.0 * jax.nn.one_hot(group, D)
    router = np.zeros((D, 8), np.float32)
    for g, (a, b) in enumerate(((0, 1), (2, 3), (5, 6))):
        router[g, a], router[g, b] = 2.0, 1.5
    return x, jnp.asarray(router), jnp.zeros((8,)).at[
        jnp.asarray([4, 7])].set(-10.0)


# T, E, held, k, tile, what is planted, a stack with first_row
_ROUTING_CASES = {
    "a-share-held-k2": (96, 16, (4, 8), 2, 8, None, False),
    "a-share-held-k8": (96, 16, (4, 8), 8, 8, None, False),
    "all-held-k2-first-row": (96, 8, (0, 8), 2, 8, None, True),
    "all-held-k8": (40, 16, (0, 16), 8, 16, None, False),
    "ties-a-share-held": (96, 16, (4, 8), 2, 8, "ties", False),
    "ties-all-held": (96, 8, (0, 8), 2, 8, "ties", True),
    "runs-of-0-tile-tile+1-a-share-held": (40, 8, (1, 5), 2, 8, "runs",
                                          False),
    "runs-of-0-tile-tile+1-all-held": (40, 8, (0, 8), 2, 8, "runs", False),
    "below-one-tile-a-tick-all-held": (3, 8, (0, 8), 2, 16, None, True),
    "below-one-tile-a-share-held": (3, 8, (2, 6), 2, 16, None, False),
}


@pytest.mark.parametrize("case", _ROUTING_CASES)
def test_routing_is_bitwise_what_the_sorts_and_scatters_gave(case):
    """``moe.moe_layer_held`` against the layer as it stood, on the CPU,
    bit for bit: the output, the counts, every token's experts in their
    order, and the gradients to tokens, router and experts."""
    T, E, held, k, tile, planted, stacked = _ROUTING_CASES[case]
    D, F, n = 32, 16, held[1] - held[0]
    ks = jax.random.split(jax.random.PRNGKey(38 + T + k), 8)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    bias = 0.1 * jax.random.normal(ks[2], (E,))
    if planted == "ties":
        # experts 5 and 6 score alike on every token, 1 and 2 on the sum
        router = router.at[:, 6].set(router[:, 5])
        bias = bias.at[6].set(bias[5]).at[1].set(0.0).at[2].set(0.0)
        router = router.at[:, 2].set(router[:, 1])
    if planted == "runs":
        x, router, bias = _planted_runs(T, D, tile, ks[0])
    experts = {"gate": jax.random.normal(ks[3], (n, D, F)) / np.sqrt(D),
               "up": jax.random.normal(ks[4], (n, D, F)) / np.sqrt(D),
               "down": jax.random.normal(ks[5], (n, F, D)) / np.sqrt(F)}
    shared = {"w_gate": jax.random.normal(ks[6], (D, F)) / np.sqrt(D),
              "w_up": jax.random.normal(ks[6], (D, F)) / np.sqrt(D),
              "w_down": jax.random.normal(ks[7], (F, D)) / np.sqrt(F)}
    cot = jax.random.normal(ks[7], (T, D))
    first_row = jnp.asarray(n) if stacked else None

    def run(layer):
        def f(x, router, experts):
            held_experts = jax.tree.map(
                lambda a: jnp.concatenate([jnp.zeros_like(a), a]),
                experts) if stacked else experts
            return layer(x, router, bias, held_experts, held, shared, k=k,
                         scale=1.8, tile=tile, picks=True,
                         first_row=first_row)

        def both(x, router, experts):
            (out, stats), vjp = jax.vjp(f, x, router, experts)
            zero = lambda a: np.zeros(a.shape, jax.dtypes.float0) \
                if jnp.issubdtype(a.dtype, jnp.integer) else jnp.zeros_like(a)
            return out, stats, vjp((cot, jax.tree.map(zero, stats)))
        return jax.jit(both)(x, router, experts)

    out, stats, grads = run(moe_layer_held)
    want, want_stats, want_grads = run(_moe_layer_held_as_it_stood)
    form = moe.combine_form(n, E)
    assert form == ("list" if n == E else "add")
    counts = np.asarray(stats["expert_counts"])
    if planted == "runs":
        assert {0, tile, tile + 1} <= set(counts.tolist()), counts
    if planted == "ties":
        picked = np.asarray(stats["experts"])
        assert ((picked == 5).any(-1) & (picked == 6).any(-1)).any()
    if case.startswith("below-one-tile"):
        assert T * k < tile
    assert float(jnp.max(jnp.abs(out))) > 0
    for name in ("expert_counts", "pairs_held", "experts", "scores"):
        assert np.array_equal(np.asarray(stats[name]),
                              np.asarray(want_stats[name])), name
    assert np.array_equal(np.asarray(out), np.asarray(want))
    for got_leaf, want_leaf in zip(jax.tree.leaves(grads),
                                   jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(want_leaf))) > 0
        assert np.array_equal(np.asarray(got_leaf), np.asarray(want_leaf))


def test_moe_tile_follows_the_rows():
    assert [G.moe_tile(n) for n in (1, 64, 65, 320, 2048, 13312)] == \
        [16, 64, 80, 256, 256, 256]


# -- what is refused, by name ---------------------------------------------------------

def test_training_is_refused_naming_the_missing_objective():
    from jax.sharding import Mesh
    import optax
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    with pytest.raises(NotImplementedError, match="multi-token prediction"):
        llama.make_train_step(CFG, mesh, optax.sgd(0.1), model=G)
    assert not hasattr(G, "loss_fn")


def test_the_engine_refuses_what_it_cannot_run_by_name(params):
    moe = llama.LlamaConfig.tiny(use_moe=True, n_experts=4)
    with pytest.raises(NotImplementedError, match="Switch expert layer"):
        ServingEngine(llama.init_params(moe, jax.random.PRNGKey(0)), moe)
    kcfg = KL.KimiLinearConfig.tiny()
    with pytest.raises(NotImplementedError, match="kimi_linear"):
        ServingEngine({}, kcfg)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(NotImplementedError, match="one chip"):
        ServingEngine(params, CFG, mesh=mesh)


def test_steps_return_each_expert_layers_counts(params):
    """The counts ride the step's own outputs: every row's k pairs, a
    layer a row of the result, for the prefill, the tick and extend."""
    tok = jnp.asarray(PROMPTS[1])[None]
    _, kept, stats = llama.prefill_step(params, tok, CFG)
    assert kept[0].shape == (3, 1, 9, 128)
    assert np.asarray(stats["expert_counts"]).sum(-1).tolist() == [18, 18]
    pool = jnp.zeros((3, 8, 4, 128))
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    args = (jnp.asarray([9, 7, 0]), jnp.asarray([9, 5, 0]), (pool,), tables)
    _, pools, stats = llama.decode_step_paged(params, *args, CFG)
    assert len(pools) == 1
    assert np.asarray(stats["expert_counts"]).sum(-1).tolist() == [6, 6]
    _, _, stats = llama.extend_step_paged(
        params, jnp.zeros((3, 2), jnp.int32), jnp.zeros((3, 2), jnp.int32),
        jnp.ones((3, 2), bool), (pool,), tables, CFG)
    assert np.asarray(stats["expert_counts"]).shape == (2, 8)
    assert llama.serve_stats(llama.LlamaConfig.tiny(), [None]) == {}


# -- compiled for a v5e, no chip needed ---------------------------------------------

def _v5e_spec(monkeypatch):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"no compile-only TPU topology: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=chip)


def test_the_cells_decode_tick_compiles_for_a_v5e(monkeypatch):
    """At the cell's size (seven layers, 64 rows, a table of 512 blocks of
    32, the pool of 18,432): one Mosaic call a layer run by the name the
    benchmark finds it by, the donated pool updated in place, and no
    layer's experts copied out for the loop over tiles (1.2 GB where a
    scan hands the loop its slice)."""
    spec = _v5e_spec(monkeypatch)
    monkeypatch.setattr(layers, "_flash_backend", lambda: True)
    cfg = G.GlmMoeLiteConfig(n_layers=7)
    assert all(G.prefill_path(cfg, b) == "flash"
               for b in (2048, 6144, 12288, 13312))
    assert G.paged_kernel_ok(cfg, None, 32)
    p = jax.tree.map(lambda s: spec(s.shape, s.dtype), jax.eval_shape(
        lambda: G.init_params(cfg, jax.random.PRNGKey(0))))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(p)) == \
        4_530_936_960
    pool = spec((7, 18432, 32, cfg.cache_row))
    i32 = lambda *shape: spec(shape, jnp.int32)
    step = jax.jit(
        lambda p, tok, pos, pool, tables: llama.decode_step_paged(
            p, tok, pos, (pool,), tables, cfg, use_flash=True),
        donate_argnums=(3,))
    compiled = step.lower(p, i32(64), i32(64), pool, i32(64, 512)).compile()
    names = [ln.split(" = ")[0].strip()
             for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(names) == 2 and all("hvd_mla_paged_decode" in n
                                   for n in names), names
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 7 * 18432 * 32 * 640 * 2
    assert mem.temp_size_in_bytes < 64 << 20, mem


def _loop_ops(text, opcode):
    """Result shapes of the compiled ops in a loop body whose ``op_name``
    ends in ``opcode``."""
    import re
    return re.findall(
        r"= (\w+\[[\d,]*\])[^\n]*op_name=\"[^\"]*while/body[^\"]*/"
        + opcode + r"\"", text)


def _route_ops(text, *opcodes):
    """``(opcode, result shapes, sorted dimension)`` of the compiled ops of
    ``opcodes`` (a prefix: ``scatter`` finds ``scatter-add`` too) whose
    ``op_name`` lies in the region ``hvd.moe.route``, fused ones too."""
    import re
    found = []
    for m in re.finditer(
            r"= (\(.*?\)|\S+) ((?:" + "|".join(opcodes) + r")[\w\-]*)\("
            r"[^\n]*op_name=\"[^\"]*hvd\.moe\.route[^\"]*\"", text):
        line = text[m.start():text.index("\n", m.start())]
        dim = re.search(r"dimensions=\{(\d+)\}", line)
        found.append((m.group(2), re.findall(r"\w+\[[\d,]*\]", m.group(1)),
                      int(dim.group(1)) if dim else None))
    return found


def test_the_cells_longest_prefill_compiles_for_a_v5e(monkeypatch):
    """The 13,312-token bucket at the cell's size: beside the weights and
    the pool the device holds, the program's temporaries and results stay
    under what the device lends (the list of 69,632 rows is 285 MB of
    them), a tile's rows go into the list, and no loop body adds rows
    into a ``[13312, 2048]``."""
    spec = _v5e_spec(monkeypatch)
    monkeypatch.setattr(layers, "_flash_backend", lambda: True)
    cfg = G.GlmMoeLiteConfig(n_layers=7)
    P, M = 13312, 13312 * 4 + 64 * 256
    p = jax.tree.map(lambda s: spec(s.shape, s.dtype), jax.eval_shape(
        lambda: G.init_params(cfg, jax.random.PRNGKey(0))))

    def prefill(p, tok, last):
        logits, kept, stats = llama.prefill_step(p, tok, cfg, last_pos=last)
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), kept
    compiled = jax.jit(prefill).lower(
        p, spec((1, P), jnp.int32), spec((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    pool = 7 * 18432 * 32 * cfg.cache_row * 2
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes + \
        mem.output_size_in_bytes + pool
    assert held < 15.6e9 < 16.9e9, (mem, pool)
    assert mem.temp_size_in_bytes < 1.0e9, mem
    text = compiled.as_text()
    assert f"bf16[{M},2048]" in _loop_ops(text, "dynamic_update_slice")
    assert not [s for s in _loop_ops(text, "scatter-add")
                if s.endswith(",2048]")], _loop_ops(text, "scatter-add")
    # Routing's passes over the T x k pairs that are not dense vector
    # work, counted: one sort, of each pair's row among the M with the
    # pair and its weight beside it.  Before PR 38: a sort of every
    # token's 64 scores along the experts' axis and an argsort, three
    # gathers and four scatters of T x k or M scalars.
    assert _route_ops(text, "sort", "gather", "scatter") == [
        ("sort", [f"s32[{M}]", f"s32[{M}]", f"f32[{M}]"], 0)]


def test_the_trainers_share_still_adds_compiled_for_a_v5e(monkeypatch):
    """The Kimi-Linear cell's layer (32,768 rows, 16 held of the 256
    scored, 8 a token, tiles of 512): the loop body adds its rows into
    the ``[T, D]`` result and no list of the bound's 270,336 rows (1.25
    GB) exists: 0.04 GB of temporaries, where the list form would keep
    3.36."""
    spec = _v5e_spec(monkeypatch)
    T, D, F, held, E, k, tile = 32768, 2304, 1024, 16, 256, 8, 512
    experts = {"gate": spec((held, D, F)), "up": spec((held, D, F)),
               "down": spec((held, F, D))}
    compiled = jax.jit(lambda x, router, bias, experts: moe_layer_held(
        x, router, bias, experts, (0, held), None, k=k, scale=2.446,
        tile=tile)[0]).lower(
            spec((T, D)), spec((D, E), jnp.float32), spec((E,), jnp.float32),
            experts).compile()
    text = compiled.as_text()
    assert f"bf16[{T},{D}]" in _loop_ops(text, "scatter-add")
    assert T * k + held * tile == 270336
    assert f"[270336,{D}]" not in text
    # Routing's passes over the T x k pairs that are not dense vector
    # work, counted: one sort, of each pair's row among the 270,336 with
    # the pair and its weight beside it.  Before PR 38: a sort of every
    # token's 256 scores along the experts' axis and an argsort, three
    # gathers and three scatters of T x k or M scalars.
    assert _route_ops(text, "sort", "gather", "scatter") == [
        ("sort", ["s32[270336]", "s32[270336]", "f32[270336]"], 0)]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9, \
        compiled.memory_analysis()

