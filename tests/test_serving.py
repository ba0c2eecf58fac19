"""serving/: paged KV cache, continuous-batching scheduler, engine.

Deterministic CPU tests.  The load-bearing assertion is greedy-token
parity: the engine must reproduce batch ``generate()``'s tokens exactly —
same model math, different cache placement — on same-length batches,
mixed-length workloads, under preemption pressure, through the Pallas
paged kernel, and on a dp/tp mesh.
"""

from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import llama, steps
from horovod_tpu.parallel import MeshConfig, build_mesh
from horovod_tpu.serving.kv_pager import (KVPager, OutOfBlocks,
                                          PagedKVCache, gather_blocks)
from horovod_tpu.serving.scheduler import Request, RequestState, Scheduler


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()            # v256 d64 L2 H4 KV2 fp32
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(rng, lens):
    return [rng.randint(0, 256, size=(n,)).astype(np.int32) for n in lens]


def _generate_oracle(params, cfg, prompt, max_new):
    return np.asarray(llama.generate(
        params, jnp.asarray(prompt[None]), cfg, max_new_tokens=max_new))[0]


# ---------------------------------------------------------------------------
# pager
# ---------------------------------------------------------------------------

def _pager(num_blocks=8, block_size=4):
    return KVPager(PagedKVCache(n_layers=2, num_blocks=num_blocks,
                                block_size=block_size,
                                rows=((2, 8), (2, 8))))


def test_pager_allocate_free_invariants():
    p = _pager()
    t1 = p.allocate(1, 7)             # 2 blocks
    t2 = p.allocate(2, 9)             # 3 blocks
    assert len(t1) == 2 and len(t2) == 3
    assert 0 not in t1 + t2, "scratch block 0 must never be handed out"
    assert len(set(t1) & set(t2)) == 0, "no block owned twice"
    p.check_invariants()
    assert p.free_blocks == 7 - 5
    p.release(1)
    assert p.free_blocks == 4
    p.check_invariants()
    # freed blocks are re-usable
    t3 = p.allocate(3, 16)            # 4 blocks
    assert set(t3) & set(t1), "released blocks should be reused"
    p.check_invariants()


def test_pager_oom_and_errors():
    p = _pager(num_blocks=4)          # 3 usable
    p.allocate(1, 8)                  # 2 blocks
    with pytest.raises(OutOfBlocks):
        p.allocate(2, 8)              # needs 2, only 1 free
    # failed allocation must not leak state
    p.check_invariants()
    assert p.free_blocks == 1
    with pytest.raises(ValueError):
        p.allocate(1, 4)              # duplicate id
    with pytest.raises(KeyError):
        p.release(99)                 # foreign free
    p.release(1)
    with pytest.raises(KeyError):
        p.release(1)                  # double free
    p.check_invariants()


def test_pager_extend_and_table_matrix():
    p = _pager()
    p.allocate(1, 4)                  # 1 block
    tbl = p.extend(1, 5)              # crosses into block 2
    assert len(tbl) == 2
    assert p.extend(1, 6) == tbl      # no growth needed
    m = p.table_matrix([1, -1], 4)
    assert m.shape == (2, 4)
    assert list(m[0][:2]) == tbl and list(m[0][2:]) == [0, 0]
    assert list(m[1]) == [0, 0, 0, 0], "inactive rows are all-scratch"


# ---------------------------------------------------------------------------
# scheduler (host-only: no jax)
# ---------------------------------------------------------------------------

def _req(i, n, max_new=4):
    return Request(req_id=i, prompt=np.arange(n, dtype=np.int32),
                   max_new_tokens=max_new)


def test_scheduler_fifo_admission_token_budget():
    p = _pager(num_blocks=64, block_size=4)
    s = Scheduler(p, max_active=8, prefill_token_budget=20)
    for i, n in enumerate([16, 16, 16, 4]):
        s.submit(_req(i, n))
    first = [r.req_id for r in s.admit()]
    # 16 + 16 exceeds the budget after the first; strict FIFO means the
    # short prompt 3 must NOT jump the queue.
    assert first == [0], f"budget admission broke FIFO: {first}"
    assert [r.req_id for r in s.admit()] == [1]


def test_scheduler_single_overbudget_prompt_still_admitted():
    p = _pager(num_blocks=64, block_size=4)
    s = Scheduler(p, max_active=4, prefill_token_budget=8)
    s.submit(_req(0, 100))            # alone and over budget
    assert [r.req_id for r in s.admit()] == [0]


def test_scheduler_blocks_gate_admission_fifo():
    p = _pager(num_blocks=8, block_size=4)   # 7 usable
    s = Scheduler(p, max_active=4, prefill_token_budget=1000)
    s.submit(_req(0, 20))             # needs 6 blocks (20+1 tokens)
    s.submit(_req(1, 4))              # would fit, but FIFO holds it back
    assert [r.req_id for r in s.admit()] == [0]
    assert [r.req_id for r in s.admit()] == [], \
        "head-of-line request must not be bypassed"
    s.finish(s.running[0])
    assert [r.req_id for r in s.admit()] == [1]


def test_scheduler_preemption_requeues_with_progress():
    p = _pager(num_blocks=8, block_size=4)   # 7 usable
    s = Scheduler(p, max_active=2, prefill_token_budget=1000)
    s.submit(_req(0, 8, max_new=20))
    s.submit(_req(1, 8, max_new=20))
    admitted = s.admit()
    assert len(admitted) == 2         # 3 blocks each (8+1 tokens)
    a, b = admitted
    a.generated = [7, 8]
    a.context_len = 10
    b.generated = [9]
    b.context_len = 9
    # grow a until the pool forces preemption of b (the youngest other)
    for n in range(11, 24):
        s.grow(a)
        a.context_len = n
    assert b.state.value == "waiting" and b.preemptions == 1
    assert s.waiting[0] is b, "preempted request re-queues at the FRONT"
    # generated tokens folded into the re-prefill prompt
    assert list(b.prefill_tokens) == list(b.prompt) + [9]


# ---------------------------------------------------------------------------
# engine vs generate(): greedy-token parity
# ---------------------------------------------------------------------------

def test_engine_matches_generate_same_length_batch(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(1)
    P, M = 8, 6
    prompts = rng.randint(0, cfg.vocab_size, size=(3, P)).astype(np.int32)
    ref = np.asarray(llama.generate(
        params, jnp.asarray(prompts), cfg, max_new_tokens=M))
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=4)
    futs = [sess.submit(p, M) for p in prompts]
    sess.drain()
    for i, f in enumerate(futs):
        assert list(f.result().full_sequence) == list(ref[i]), \
            f"token mismatch on request {i}"


def test_engine_matches_generate_mixed_lengths(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(2)
    lens = [5, 11, 3, 16, 9]
    mx = [4, 7, 12, 3, 6]
    prompts = _prompts(rng, lens)
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=3)
    futs = [sess.submit(p, m) for p, m in zip(prompts, mx)]
    sess.drain()
    for i, f in enumerate(futs):
        ref = _generate_oracle(params, cfg, prompts[i], mx[i])
        assert list(f.result().full_sequence) == list(ref), \
            f"token mismatch on request {i} (len {lens[i]})"


def test_engine_parity_under_preemption_pressure(tiny):
    """A pool too small for the whole workload forces preemptions; the
    re-prefilled continuation must still match generate() exactly."""
    cfg, params = tiny
    rng = np.random.RandomState(3)
    lens = [6, 6, 6]
    mx = [10, 10, 10]
    prompts = _prompts(rng, lens)
    # 11 usable blocks of 2 = 22 token slots; 3 requests need 16+ each.
    sess = serving.serve(params, cfg, block_size=2, num_blocks=12,
                         max_active=3)
    futs = [sess.submit(p, m) for p, m in zip(prompts, mx)]
    sess.drain()
    preemptions = 0
    for i, f in enumerate(futs):
        res = f.result()
        preemptions += res.metrics["preemptions"]
        ref = _generate_oracle(params, cfg, prompts[i], mx[i])
        assert list(res.full_sequence) == list(ref), \
            f"token mismatch on request {i} after preemption"
    assert preemptions > 0, "pool was sized to force preemption"


def test_decode_tick_compares_no_two_requests(tiny, monkeypatch):
    """A tick that admits and finishes nothing never calls
    ``Request.__eq__``: the engine tells a stream that ``grow()``
    preempted by its state, not by asking the running list, whose
    field-by-field test is O(streams^2) a tick."""
    cfg, params = tiny
    rng = np.random.RandomState(5)
    eng = serving.ServingEngine(params, cfg, engine_cfg=serving.EngineConfig(
        block_size=4, num_blocks=32, max_active=4))
    for p in _prompts(rng, [5, 6, 7, 8]):
        eng.submit(p, 8)
    while eng.scheduler.waiting:
        eng.step()
    calls = []
    eq = Request.__eq__
    monkeypatch.setattr(Request, "__eq__",
                        lambda a, b: calls.append(1) or eq(a, b))
    assert len(eng.step()) == 4
    assert calls == []


def test_engine_bucketed_prefill_matches_exact(tiny):
    """Right-padded bucketed prefill must emit the same tokens as
    exact-length compiles (causality makes the padded tail inert)."""
    cfg, params = tiny
    rng = np.random.RandomState(4)
    lens = [3, 5, 9]
    prompts = _prompts(rng, lens)
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=3, prefill_buckets=(8, 16))
    futs = [sess.submit(p, 5) for p in prompts]
    sess.drain()
    for i, f in enumerate(futs):
        ref = _generate_oracle(params, cfg, prompts[i], 5)
        assert list(f.result().full_sequence) == list(ref)


def test_engine_paged_flash_kernel_mode(tiny):
    """use_flash="interpret" routes decode attention through the Pallas
    paged kernel (block tables by scalar prefetch); tokens must match the
    XLA gather path bit for bit."""
    cfg, params = tiny
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, [6, 10])
    sess = serving.serve(params, cfg, block_size=8, num_blocks=32,
                         max_active=2, use_flash="interpret")
    futs = [sess.submit(p, 6) for p in prompts]
    sess.drain()
    for i, f in enumerate(futs):
        ref = _generate_oracle(params, cfg, prompts[i], 6)
        assert list(f.result().full_sequence) == list(ref)


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_decode_tick_is_extend_with_one_token_a_row(tiny, use_flash):
    """The two paged steps are one skeleton: a decode tick (through the
    gather and through the interpreted kernel) and ``extend_step_paged``
    with one token a row write the same pools and return the same
    logits."""
    cfg, params = tiny
    rng = np.random.RandomState(3)
    B, NB, BS, C = 3, 12, 4, 3
    shape = (cfg.n_layers, NB, BS, cfg.n_kv_heads, cfg.head_dim)
    kp = jnp.asarray(rng.randn(*shape), jnp.float32)
    vp = jnp.asarray(rng.randn(*shape), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(NB - 1)[:B * C].reshape(B, C),
                         jnp.int32)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(B,)), jnp.int32)
    pos = jnp.asarray([5, 2, 9], jnp.int32)
    logits_d, (kp_d, vp_d), _, _ = steps.decode_step_paged(
        params, tok, pos, (kp, vp), tables, cfg, use_flash=use_flash,
        interpret=use_flash)
    logits_e, (kp_e, vp_e), _ = steps.extend_step_paged(
        params, tok[:, None], pos[:, None], jnp.ones((B, 1), bool), (kp, vp),
        tables, cfg)
    assert logits_d.shape == (B, cfg.vocab_size)
    np.testing.assert_allclose(logits_d, logits_e[:, 0], rtol=1e-4,
                               atol=1e-4)
    # The first layer's rows are the same numbers on either path; later
    # layers' follow an attention that the kernel sums in another order.
    same = np.testing.assert_array_equal
    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-5)
    for got, want in ((kp_d, kp_e), (vp_d, vp_e)):
        same(got[0], want[0])
        (close if use_flash else same)(got, want)
    assert not np.array_equal(np.asarray(kp_d), np.asarray(kp))


def test_decode_tick_skips_rows_with_no_stream(tiny):
    """A slot of the batch with no stream reaches the decode program as
    the engine builds it: token 0, position 0, a table row of scratch
    block 0.  Through the kernel such a row is length 0 (told by its
    table's first entry), not a stream of one token: nothing of block 0
    is read, so NaN there stays there; the live rows' logits and pages
    are those of the gather path."""
    cfg, params = tiny
    rng = np.random.RandomState(11)
    B, NB, BS, C = 5, 16, 4, 3
    shape = (cfg.n_layers, NB, BS, cfg.n_kv_heads, cfg.head_dim)
    kp, vp = rng.randn(2, *shape).astype(np.float32)
    live = np.asarray([False, True, False, False, True])
    tables = np.zeros((B, C), np.int32)
    tables[live] = 1 + rng.permutation(NB - 1)[:2 * C].reshape(2, C)
    tok = np.where(live, rng.randint(0, cfg.vocab_size, size=B), 0)
    pos = np.where(live, [0, 6, 0, 0, 9], 0)
    args = [jnp.asarray(a, jnp.int32) for a in (tok, pos)]
    step = lambda k, v, flash: steps.decode_step_paged(
        params, *args, (jnp.asarray(k), jnp.asarray(v)),
        jnp.asarray(tables), cfg, use_flash=flash, interpret=flash)
    logits_g, (kp_g, vp_g), _, _ = step(kp, vp, False)
    kp[:, 0, 1:] = vp[:, 0, 1:] = np.nan      # offset 0 is written first
    logits_k, (kp_k, vp_k), _, _ = step(kp, vp, True)
    assert np.isfinite(np.asarray(logits_k)).all()
    np.testing.assert_allclose(logits_k[live], logits_g[live], rtol=1e-4,
                               atol=1e-4)
    for got, want in ((kp_k, kp_g), (vp_k, vp_g)):
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=1e-4,
                                   atol=1e-5)


def test_paged_attention_kernel_vs_gather_oracle():
    from horovod_tpu.models.layers import cached_attend
    from horovod_tpu.ops import flash_attention as FA
    rng = np.random.RandomState(0)
    B, H, KV, Dh, NB, BS, C = 3, 8, 2, 64, 16, 8, 4
    q = jnp.asarray(rng.randn(B, H, Dh), jnp.float32)
    L, li = 3, 1
    kp = jnp.asarray(rng.randn(L, NB, BS, KV, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(L, NB, BS, KV, Dh), jnp.float32)
    tables = jnp.asarray(
        rng.choice(np.arange(1, NB), size=(B * C,),
                   replace=False).reshape(B, C), jnp.int32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    out = FA.paged_attention(q, kp, vp, li, tables, lengths, interpret=True)
    keys, vals = gather_blocks(kp[li], tables), gather_blocks(vp[li], tables)
    mask = (jnp.arange(C * BS)[None, :] < lengths[:, None])[:, None, :]
    ref = cached_attend(q[:, None], keys, vals, mask,
                         1.0 / np.sqrt(Dh))[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# (id, KV, rep, dtype, BS, n_cols, group tokens or None for the kernel's
# own, lengths; None in lengths is a row with no stream: length 0, table
# all scratch block 0).  With G = the group's tokens: lengths of 1, one
# short of a page, exactly a group, one past a group, the whole table.
_PAGED_CASES = [
    ("kv2-rep4-f32", 2, 4, "float32", 4, 12, 16, [1, 3, 16, 17, 48]),
    ("kv8-rep1-f32", 8, 1, "float32", 4, 12, 16, [1, 3, 16, 17, 48]),
    ("kv8-rep4-f32", 8, 4, "float32", 8, 6, 16, [7, 16, 17, 33, 48]),
    ("kv2-rep1-f32", 2, 1, "float32", 8, 6, 16, [1, 15, 32, 48]),
    ("kv2-rep4-bf16", 2, 4, "bfloat16", 4, 12, 16, [1, 3, 16, 17, 48]),
    ("kv8-rep4-bf16", 8, 4, "bfloat16", 8, 6, 16, [7, 16, 17, 33, 48]),
    ("kv8-rep1-bf16", 8, 1, "bfloat16", 4, 12, 16, [1, 3, 16, 17, 48]),
    ("inactive-rows", 2, 4, "float32", 4, 12, 16, [None, 17, None, 48]),
    ("inactive-rows-bf16", 2, 4, "bfloat16", 4, 12, 16, [None, 17, None]),
    # the table narrower than a group's P = 8 pages: P becomes n_cols
    ("table-narrower-than-group", 2, 4, "float32", 4, 3, 32, [1, 5, 12]),
    # ... and not a multiple of P = 2: the last group holds one page
    ("table-not-a-multiple", 2, 4, "float32", 8, 5, 16, [8, 31, 33, 40]),
    ("table-not-a-multiple-bf16", 8, 4, "bfloat16", 8, 5, 16, [8, 33, 40]),
    # the kernel's own group size (256 tokens, P = 32) on a table 2.5
    # groups wide
    ("default-group", 2, 4, "float32", 8, 80, None, [1, 255, 256, 257, 640]),
    ("default-group-bf16", 2, 4, "bfloat16", 8, 80, None, [256, 257, 640]),
    # Rows with no stream, wherever slots fall free (PR 34), at the two
    # served geometries (Mistral KV 8 / H 32, Ouro KV 16 / H 16): first,
    # last, two in a row, alternating, all of them.  G = 32 tokens, P = 8.
    ("idle-first-kv8-h32", 8, 4, "bfloat16", 4, 24, 32, [None, 33, 5]),
    ("idle-last-kv16-h16", 16, 1, "bfloat16", 4, 24, 32, [40, 7, None]),
    ("idle-two-in-a-row-kv16-h16", 16, 1, "bfloat16", 4, 24, 32,
     [64, None, None, 29, None]),
    ("idle-two-in-a-row-kv8-h32", 8, 4, "float32", 4, 24, 32,
     [None, None, 96, None, None, 3]),
    ("idle-alternating-kv8-h32", 8, 4, "bfloat16", 4, 24, 32,
     [None, 31, None, 65, None, 1, None]),
    ("idle-alternating-kv16-h16", 16, 1, "float32", 4, 24, 32,
     [12, None, 45, None, 32, None]),
    ("idle-all-kv8-h32", 8, 4, "bfloat16", 4, 24, 32, [None, None, None]),
    ("idle-all-kv16-h16", 16, 1, "bfloat16", 4, 24, 32, [None]),
    # a last group of one live page (G + 1 and 2G + 3), a whole number
    # of groups (G, 2G, 3G), lengths 1 and G + 1, page counts with every
    # binary digit (7 = 4 + 2 + 1 pages of the last group's 8)
    ("last-group-one-page-kv8-h32", 8, 4, "bfloat16", 4, 24, 32,
     [33, 67, 1, 36]),
    ("last-group-one-page-kv16-h16", 16, 1, "bfloat16", 4, 24, 32,
     [33, 1, 67, 34]),
    ("whole-groups-kv8-h32", 8, 4, "bfloat16", 4, 24, 32, [32, 64, 96]),
    ("whole-groups-kv16-h16", 16, 1, "float32", 4, 24, 32, [96, 32, 64]),
    ("page-counts-kv16-h16", 16, 1, "bfloat16", 4, 24, 32,
     [4, 8, 12, 20, 28, 60, 93]),
    ("page-counts-kv8-h32", 8, 4, "float32", 4, 24, 32,
     [9, 13, 24, 55, 91, None, 17]),
]


@pytest.mark.parametrize("poison", [None, "nan", "1e30"])
@pytest.mark.parametrize(
    "KV,rep,dtype,BS,C,group,lens",
    [pytest.param(*c[1:], id=c[0]) for c in _PAGED_CASES])
def test_paged_attention_kernel_cases(monkeypatch, KV, rep, dtype, BS, C,
                                      group, lens, poison):
    """The paged kernel against the gather oracle over its geometry and
    the lengths at which its walk changes shape.  ``poison`` fills every
    page of the pool that holds no live token (those wholly past their
    stream's length, those no table names, and the scratch block 0 that
    a row with no stream is made of) with NaN or 1e30 for the kernel
    alone: the kernel copies live pages only, so the result is the clean
    pool's, and zeros on a row with no stream.

    bf16 pools: both sides round ``p`` (at most 1) to bf16, the kernel
    before the row sum divides it and the oracle after, and round the
    result; each of the four roundings is within 2**-9 relative, on a
    convex combination of V's rows, so the gap is under 4 * 2**-9 *
    max|V|.  float32 pools round nothing: only the summation order
    differs."""
    from horovod_tpu.models.layers import cached_attend
    from horovod_tpu.ops import flash_attention as FA
    if group is not None:
        monkeypatch.setattr(FA, "_PAGED_GROUP_TOKENS", group)
    rng = np.random.RandomState(len(lens) * 131 + KV * 7 + rep + BS + C)
    dtype = jnp.dtype(dtype)
    B, H, Dh, L, li = len(lens), KV * rep, 32, 2, 1
    NB = 1 + B * C
    q = jnp.asarray(rng.randn(B, H, Dh), dtype)
    kp = rng.randn(L, NB, BS, KV, Dh).astype(np.float32)
    vp = rng.randn(L, NB, BS, KV, Dh).astype(np.float32)
    tables = (1 + rng.permutation(B * C)).reshape(B, C).astype(np.int32)
    lengths = np.asarray([0 if n is None else n for n in lens], np.int32)
    idle = lengths == 0
    tables[idle] = 0
    live = np.zeros(NB, bool)                 # blocks holding a live token
    for b, n in enumerate(lengths):
        live[tables[b, :-(-int(n) // BS)]] = True
    assert not live[0]
    clean_k, clean_v = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    if poison is not None:                    # copies: jnp may alias numpy
        kp, vp = kp.copy(), vp.copy()
        kp[:, ~live] = vp[:, ~live] = float(poison)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    out = FA.paged_attention(q, jnp.asarray(kp, dtype),
                             jnp.asarray(vp, dtype), li, tables, lengths,
                             interpret=True)
    keys = gather_blocks(clean_k[li], tables)
    vals = gather_blocks(clean_v[li], tables)
    # The oracle's softmax over a row with nothing live is a mean of V:
    # give it one live column and compare that row with zeros instead.
    mask = (jnp.arange(C * BS)[None, :]
            < jnp.maximum(lengths, 1)[:, None])[:, None, :]
    ref = cached_attend(q[:, None], keys, vals, mask,
                         1.0 / np.sqrt(Dh))[:, 0]
    ref = jnp.where(idle[:, None, None], 0, ref)
    assert out.dtype == q.dtype and out.shape == q.shape
    if dtype == jnp.float32:
        tol = dict(rtol=1e-5, atol=2e-6)
    else:
        tol = dict(rtol=0, atol=4 * 2.0 ** -9 * float(
            np.abs(vp[li, live]).max(initial=0.0)))
    out = np.asarray(out, np.float32)
    assert not out[idle].any()
    np.testing.assert_allclose(out, np.asarray(ref, np.float32), **tol)


def test_paged_group_pages_follow_the_shapes():
    """The group size comes from the shapes alone: the kernel's token
    target over the page size, no wider than the table, halved until the
    resident set fits VMEM; geometries Mosaic refuses are gated."""
    from horovod_tpu.ops import flash_attention as FA
    tokens = FA._PAGED_GROUP_TOKENS
    # the backlog cell: Mistral 7B, 16-token pages, bf16
    assert FA.paged_group_pages(16, 128, 8, 32, 2, 256) == tokens // 16
    assert FA.paged_group_pages(16, 128, 8, 32, 2, 5) == 5
    assert FA.paged_group_pages(512, 128, 8, 32, 2, 64) == 1
    # 64 q and kv heads of 256: a 256-token group would be 32 MiB
    wide = FA.paged_group_pages(16, 256, 64, 64, 2, 256)
    assert 1 <= wide < tokens // 16
    assert FA._paged_resident(wide, 16, 256, 64, 64, 2) <= FA._VMEM_BUDGET
    assert FA._paged_resident(2 * wide, 16, 256, 64, 64, 2) > FA._VMEM_BUDGET
    assert FA.paged_supported(16, 128, 8, 32, 2)
    assert FA.paged_supported(16, 128, 2, 8, 2)         # tp=4 share of it
    assert FA.paged_supported(16, 128, 8, 32, 4)
    assert not FA.paged_supported(16, 64, 8, 32, 2)     # Dh under a lane row
    assert not FA.paged_supported(16, 128, 1, 8, 2)     # odd bf16 kv heads
    assert not FA.paged_supported(4096, 256, 64, 64, 2)  # one page over VMEM


def test_engine_on_mesh_matches_generate(tiny):
    """dp=4/tp=2 mesh: pool kv_heads over tp (never replicated), decode
    batch over dp — tokens must match the plain single-device engine and
    generate()."""
    cfg, params = tiny
    mesh = build_mesh(MeshConfig(dp=4, tp=2))
    params_s = jax.device_put(params, llama.param_shardings(cfg, mesh))
    rng = np.random.RandomState(6)
    prompts = _prompts(rng, [7, 4, 12, 9])
    sess = serving.serve(params_s, cfg, mesh=mesh, block_size=4,
                         num_blocks=64, max_active=4)
    futs = [sess.submit(p, 5) for p in prompts]
    sess.drain()
    for i, f in enumerate(futs):
        ref = _generate_oracle(params, cfg, prompts[i], 5)
        assert list(f.result().full_sequence) == list(ref), \
            f"mesh token mismatch on request {i}"


# ---------------------------------------------------------------------------
# streaming, metrics, timeline
# ---------------------------------------------------------------------------

def test_streaming_callback_ordering(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, [4, 8])
    events: list[tuple[int, int]] = []
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=2)
    futs = [sess.submit(p, 6, stream_cb=lambda rid, tok:
                        events.append((rid, tok))) for p in prompts]
    sess.drain()
    for f in futs:
        res = f.result()
        streamed = [t for rid, t in events if rid == res.req_id]
        assert streamed == res.tokens, \
            "per-request stream must be the token sequence, in order"
    # interleaving property: each request's events appear in generation
    # order even when interleaved with the other request's
    first_positions = {}
    for i, (rid, _) in enumerate(events):
        first_positions.setdefault(rid, i)
    assert len(first_positions) == 2


def test_metrics_and_timeline_spans(tiny, tmp_path):
    from horovod_tpu.utils.timeline import Timeline
    cfg, params = tiny
    rng = np.random.RandomState(8)
    path = str(tmp_path / "serving_timeline.json")
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=2, timeline=Timeline(path))
    fut = sess.submit(_prompts(rng, [6])[0], 4)
    sess.drain()
    m = fut.result().metrics
    assert m["new_tokens"] == 4
    assert m["queue_wait_s"] >= 0
    assert m["ttft_s"] is not None and m["ttft_s"] >= 0
    assert m["decode_tokens_per_s"] is None or m["decode_tokens_per_s"] > 0
    sess.close()
    text = open(path).read()
    assert "QUEUE" in text and "DECODE" in text and "req0" in text


def test_submit_validation(tiny):
    cfg, params = tiny
    sess = serving.serve(params, cfg, block_size=4, num_blocks=8,
                         max_active=1)
    with pytest.raises(ValueError, match="empty"):
        sess.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        sess.submit(np.arange(4, dtype=np.int32), 0)


def test_submit_rejects_prompt_larger_than_pool(tiny):
    """An unfillable prompt must be rejected up front: at the head of the
    strictly-FIFO queue it would otherwise livelock admission forever."""
    cfg, params = tiny
    sess = serving.serve(params, cfg, block_size=4, num_blocks=8,
                         max_active=2)                 # 7 usable = 28 slots
    with pytest.raises(ValueError, match="blocks"):
        sess.submit(np.arange(40, dtype=np.int32) % 100, 4)
    # and a fitting request behind the rejection still works
    fut = sess.submit(np.arange(6, dtype=np.int32), 2)
    sess.drain()
    assert len(fut.result().tokens) == 2


def test_scheduler_fails_unfittable_requeued_request():
    """A preempted request whose folded-in progress no longer fits the
    pool must be FAILED (drained via engine.pop_failed), not left to
    livelock the FIFO head."""
    p = _pager(num_blocks=4, block_size=4)             # 3 usable = 12 slots
    s = Scheduler(p, max_active=2, prefill_token_budget=1000)
    r = _req(0, 4, max_new=30)
    s.submit(r)
    r.prefill_tokens = np.arange(20, dtype=np.int32)   # preemption fold
    assert s.admit() == []
    assert s.waiting == deque() or not s.waiting
    assert len(s.failed) == 1 and s.failed[0][0] is r
    assert isinstance(s.failed[0][1], OutOfBlocks)


def test_background_thread_failure_sets_future_exception(tiny):
    """A request that outgrows the pool while running ALONE raises
    OutOfBlocks in the engine; the background thread must surface it on
    the pending future instead of dying silently."""
    cfg, params = tiny
    # 3 usable blocks = 12 token slots; prompt 4 + max_new 12 overflows.
    sess = serving.serve(params, cfg, block_size=4, num_blocks=4,
                         max_active=1)
    fut = sess.submit(np.arange(4, dtype=np.int32), 12)
    sess.start()
    with pytest.raises(OutOfBlocks):
        fut.result(timeout=120)
    sess.close()


def test_eos_token_stops_early(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(9)
    prompt = _prompts(rng, [6])[0]
    ref = _generate_oracle(params, cfg, prompt, 8)
    eos = int(ref[len(prompt) + 2])   # the 3rd generated token
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=1)
    fut = sess.submit(prompt, 8, eos_token=eos)
    sess.drain()
    res = fut.result()
    assert res.tokens == list(ref[len(prompt):len(prompt) + 3]), \
        "generation must stop AT the eos token"


# ---------------------------------------------------------------------------
# one tick in flight: each turn dispatches its tick before it fetches the
# last turn's, and drains where growth may preempt and on an abort
# ---------------------------------------------------------------------------

def _counter(name, **labels):
    from horovod_tpu.obs import REGISTRY
    m = REGISTRY.get(name)
    return (m.labels(**labels) if labels else m).value


_DRAINS = ("preempt", "abort")


def _lookahead_counts():
    return dict(ahead=_counter("hvd_serving_decode_ahead_total"),
                dropped=_counter("hvd_serving_lookahead_dropped_rows_total"),
                **{r: _counter("hvd_serving_lookahead_drains_total",
                               reason=r) for r in _DRAINS})


def _counting_ticks(eng):
    """``eng``'s decode program, counting its dispatches."""
    ticks, real = [], eng._decode
    eng._decode = lambda *a: ticks.append(1) or real(*a)
    return ticks


@pytest.mark.parametrize("max_active", [1, 2, 5])
def test_ticks_run_ahead_and_keep_generates_tokens(tiny, max_active):
    """Mixed prompts and budgets over 1, 2 and 5 rows: every tick but
    the first is dispatched with the one before still in flight, nothing
    drains, and the tokens are generate()'s; a request whose last token
    is in flight hands its row to the next at once."""
    cfg, params = tiny
    rng = np.random.RandomState(12)
    lens, mx = [5, 11, 3, 16, 9, 7], [4, 7, 2, 3, 6, 5]
    prompts = _prompts(rng, lens)
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=max_active)
    ticks = _counting_ticks(sess.engine)
    before = _lookahead_counts()
    futs = [sess.submit(p, m) for p, m in zip(prompts, mx)]
    sess.drain()
    got = {k: v - before[k] for k, v in _lookahead_counts().items()}
    assert got == dict(ahead=len(ticks) - 1, dropped=0,
                       **{r: 0 for r in _DRAINS})
    for p, m, f in zip(prompts, mx, futs):
        assert list(f.result().full_sequence) == list(
            _generate_oracle(params, cfg, p, m))
    assert not sess.engine.has_work()
    sess.engine.pager.check_invariants()


def test_a_last_token_in_flight_gives_up_the_row_at_dispatch(tiny):
    """A budget of two: the first tick holds the request's last token,
    so the request leaves the batch, its blocks and its row as that tick
    is dispatched, the next turn prefills another request into the row
    while the tick still runs, and the request finishes when that turn
    fetches the tick."""
    cfg, params = tiny
    rng = np.random.RandomState(13)
    eng = serving.ServingEngine(params, cfg, engine_cfg=serving.EngineConfig(
        block_size=4, num_blocks=16, max_active=1))
    a, b = (eng.submit(p, m) for p, m in zip(_prompts(rng, [6, 4]),
                                               [2, 3]))
    emitted = eng.step()
    assert [(r.req_id, t) for r, t in emitted] == [(0, a.generated[0])]
    assert eng.scheduler.running == [] and eng._slots == [None]
    assert eng.pager.free_blocks == 15 and a.released
    assert a.state is RequestState.RUNNING and eng.has_work()
    emitted = eng.step()                 # b prefilled into a's row
    assert [(r.req_id, t) for r, t in emitted] == [
        (0, a.generated[1]), (1, b.generated[0])]
    assert a.finish_reason == "length" and eng._slots == [b]
    eng.run()
    for r in (a, b):
        assert list(r.prompt) + r.generated == list(
            _generate_oracle(params, cfg, r.prompt, r.max_new_tokens))


def test_preemption_drains_first_and_keeps_the_tokens(tiny):
    """A pool that forces preemptions: each ``grow`` that may preempt
    first fetches the tick in flight (its tokens fold into the
    re-prefill), the re-prefilled outputs are generate()'s, and every
    tick but the first and those after a drain ran ahead."""
    cfg, params = tiny
    rng = np.random.RandomState(3)
    prompts = _prompts(rng, [6, 6, 6])
    sess = serving.serve(params, cfg, block_size=2, num_blocks=12,
                         max_active=3)
    ticks = _counting_ticks(sess.engine)
    before = _lookahead_counts()
    futs = [sess.submit(p, 10) for p in prompts]
    sess.drain()
    got = {k: v - before[k] for k, v in _lookahead_counts().items()}
    assert got["preempt"] > 0
    assert got["ahead"] == len(ticks) - 1 - got["preempt"]
    assert sum(f.result().metrics["preemptions"] for f in futs) > 0
    for p, f in zip(prompts, futs):
        assert list(f.result().full_sequence) == list(
            _generate_oracle(params, cfg, p, 10))


def test_a_pick_past_eos_is_dropped(tiny):
    """The tick after the one that picked EOS is already dispatched when
    EOS is fetched: its pick for that row is counted and dropped, never
    emitted or streamed, and the other row's stream goes on unchanged."""
    cfg, params = tiny
    rng = np.random.RandomState(9)
    prompts = _prompts(rng, [6, 5])
    ref = [_generate_oracle(params, cfg, p, 8) for p in prompts]
    eos = int(ref[0][len(prompts[0]) + 2])          # its 3rd token
    assert eos not in list(ref[0][len(prompts[0]):len(prompts[0]) + 2])
    events: list = []
    sess = serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=2)
    before = _counter("hvd_serving_lookahead_dropped_rows_total")
    futs = [sess.submit(p, 8, eos_token=eos if i == 0 else None,
                        stream_cb=lambda rid, t: events.append((rid, t)))
            for i, p in enumerate(prompts)]
    sess.drain()
    stopped, other = (f.result() for f in futs)
    assert stopped.tokens == list(ref[0][len(prompts[0]):][:3])
    assert stopped.metrics["finish_reason"] == "stop"
    assert other.tokens == list(ref[1][len(prompts[1]):])
    for res in (stopped, other):
        assert [t for rid, t in events if rid == res.req_id] == res.tokens
    assert _counter("hvd_serving_lookahead_dropped_rows_total") - before \
        == 1


def test_abort_fetches_the_tick_in_flight_first(tiny):
    """``abort_inflight`` with a tick in flight: its tokens are emitted
    first (a request whose last token was among them finishes by its
    length), the rest end as errors with every token they had, and the
    engine serves again from empty."""
    cfg, params = tiny
    rng = np.random.RandomState(14)
    prompts = _prompts(rng, [5, 7, 4])
    eng = serving.ServingEngine(params, cfg, engine_cfg=serving.EngineConfig(
        block_size=4, num_blocks=32, max_active=2))
    a, b = (eng.submit(p, m) for p, m in zip(prompts, [2, 8]))
    first = eng.step()                    # a's last token is in tick 1
    assert [(r.req_id, t) for r, t in first] == [
        (0, a.generated[0]), (1, b.generated[0])]
    before = _counter("hvd_serving_lookahead_drains_total", reason="abort")
    emitted, aborted = eng.abort_inflight(RuntimeError("device lost"))
    assert [(r.req_id, t) for r, t in emitted] == [
        (0, a.generated[1]), (1, b.generated[1])]
    assert aborted == [b] and b.finish_reason == "error"
    assert a.finish_reason == "length"
    for r in (a, b):
        assert list(r.prompt) + r.generated == list(
            _generate_oracle(params, cfg, r.prompt, 2))
    assert _counter("hvd_serving_lookahead_drains_total",
                    reason="abort") - before == 1
    assert not eng.has_work() and eng.pager.free_blocks == 31
    c = eng.submit(prompts[2], 3)
    eng.run()
    assert list(c.prompt) + c.generated == list(
        _generate_oracle(params, cfg, prompts[2], 3))
