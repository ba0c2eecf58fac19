"""Flash attention (forward + backward) as Pallas TPU kernels.

No reference analogue — the reference has no compute kernels at all; this
exists because the flagship's attention is the hottest op and materializing
``[B, H, S, S]`` fp32 scores is HBM-bound at long sequence.  The kernels
stream K/V through VMEM with online-softmax accumulation (Dao et al.,
arXiv:2205.14135), so HBM traffic is O(S·D) instead of O(S²) and the
block matmuls stay on the MXU.

Layout choices (see /opt/skills/guides/pallas_guide.md):
- forward grid = (B·H, S/BLOCK_Q): one program per query block per head;
  K/V for the whole sequence sit in VMEM and the kernel loops over K blocks
  with ``fori_loop``, saving the log-sum-exp per row for the backward.
- backward = two kernels (the standard split): dq over query blocks and
  dk/dv over key blocks, each recomputing its score block from q/k + LSE —
  no O(S²) tensor ever hits HBM.
- block sizes are multiples of the (16, 128) bf16 tile; matmuls use
  ``preferred_element_type=jnp.float32`` so the MXU accumulates fp32 while
  inputs stay bf16.

Measured on TPU v5 lite vs XLA's fused dense attention (bf16,
B=4,H=16,D=64, causal), forward+backward — the training shape, with
bf16-MXU dots and the per-length block tuning in :func:`default_blocks`
(round 4): 1.01x at S=512, 1.82x at 1024, 2.54x at 2048, 5.28x at 4096.
Data committed in ``benchmarks/measured.jsonl``; reproduce with
``python benchmarks/flash_bench.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30


def gqa_expand(q, k, v):
    """Materialize grouped K/V up to q's head count — for attention paths
    without native GQA indexing (the dense oracle, ring/Ulysses sp, and
    flash on meshes where tp divides H but not KV); the Pallas kernels
    index kv heads directly and never pay this rep x HBM expansion."""
    H, KV = q.shape[2], k.shape[2]
    if KV != H:
        if H % KV:
            raise ValueError(
                f"kv heads {KV} must divide q heads {H}")
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def dense_attention(q, k, v, scale, causal):
    """Dense XLA attention — the fallback path and the test oracle.
    Accepts grouped K/V (kv_heads dividing q heads) via
    :func:`gqa_expand`."""
    k, v = gqa_expand(q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _to_bhsd(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bhsd(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                block_k: int, seq_len: int, scale: float, causal: bool):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    # Dots take the INPUT dtype with fp32 MXU accumulation: casting bf16
    # operands to fp32 before the matmul forces fp32-rate MXU passes
    # (~2-4x slower on v5e); the canonical flash formulation keeps q/k/v
    # bf16 and scales the fp32 score block instead.
    q = q_ref[0]                                      # [BQ, D]
    n_kv = seq_len // block_k

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        blk_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.dot(p.astype(v_blk.dtype), v_blk,
                     preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha[:, None] + pv

    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    if causal:
        # last needed K block covers query row (qi+1)*block_q - 1
        upper = jax.lax.min(
            ((qi + 1) * block_q - 1) // block_k + 1, n_kv)
    else:
        upper = n_kv
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # TPU block tiling wants (8, 128)-aligned 2-D tails, so LSE is stored
    # broadcast across 8 sublanes: [BH, 8, S].
    lse_ref[0] = jnp.broadcast_to((m + jnp.log(l_safe))[None, :],
                                  (8, lse_ref.shape[-1]))


# Scoped VMEM a Mosaic kernel gets on the v5e without asking; a kernel
# that needs more asks for it (``vmem_limit_bytes``) out of the chip's
# 128 MiB.  :data:`_FLASH_VMEM_BUDGET` is what :func:`supported` lets the
# flash kernels keep resident, by :func:`_flash_resident`: 40 MiB since a
# 13,312-token prompt at keys and values 256 wide keeps 33 (the forward
# kernel compiles there for a v5e with its limit raised to 50; a prompt
# that fell to the dense path would build 12 GB of scores).
_SCOPED_VMEM = 16 << 20
_FLASH_VMEM_BUDGET = 40 << 20


def _flash_resident(S: int, D: int, Dv: int, itemsize: int, blk: int) -> int:
    """VMEM bytes of the heaviest of the three kernels, the one estimate
    that :func:`supported` and :func:`_compiler_params` both go by: the
    two full-sequence operands a kernel keeps resident (K/V in the
    forward and dq, Q/dO in dk/dv: one of the key width ``D``, one of the
    value width ``Dv``), each padded to whole 128-lane tiles and held in
    two buffers, the lse and delta rows likewise, and the float32 block
    operands and accumulators.  Checked against the compiler:
    ahead-of-time compiles for a v5e (jax 0.9.0, libtpu 0.0.34, 32
    heads) name 16.02 MiB for S=8192 D=192 Dv=128 bf16 (17.0 here) and
    17.75 MiB for S=16384 D=128 bf16 (20.0 here)."""
    lanes = lambda d: -(-d // 128) * 128
    return (2 * S * (lanes(D) + lanes(Dv)) * itemsize
            + 2 * 2 * 8 * S * 4
            + 2 * 4 * blk * lanes(D) * 4)


def _compiler_params(S: int, D: int, Dv: int, itemsize: int,
                     blk: int) -> dict:
    """``compiler_params`` for the three kernels: nothing while
    :func:`_flash_resident` stays an eighth under the default scoped
    VMEM, which those shapes fit; above that (latent attention at 8k:
    keys 192 wide pad to 256 lanes) the limit is raised to the estimate
    and half as much again."""
    from jax.experimental.pallas import tpu as pltpu
    resident = _flash_resident(S, D, Dv, itemsize, blk)
    if resident <= _SCOPED_VMEM * 7 // 8:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=resident * 3 // 2)}


def _kv_row_map(H: int, KV: int):
    """BlockSpec index map sending a flattened q-head row ``b*H + h`` to
    its kv row ``b*KV + h // rep`` — the GQA-native indexing: K/V stay
    [B*KV, S, D] in HBM (rep x smaller than the ``jnp.repeat`` expansion)
    and adjacent q-head programs of one group hit the SAME kv block, so
    Pallas skips the re-fetch between consecutive grid steps."""
    rep = H // KV
    return lambda bh, qi: ((bh // H) * KV + (bh % H) // rep, 0, 0)


def _flash_forward(q, k, v, *, scale, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    KV, Dv = k.shape[2], v.shape[3]
    qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, seq_len=S,
        scale=scale, causal=causal)
    kv_map = _kv_row_map(H, KV)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, D), kv_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, Dv), kv_map, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda bh, qi: (bh, 0, qi),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, S), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_flash_fwd",
        **_compiler_params(S, D, Dv, q.dtype.itemsize,
                           max(block_q, block_k)),
    )(qt, kt, vt)
    return _from_bhsd(out, B, H), lse[:, 0, :]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, block_q: int, block_k: int, seq_len: int, scale: float,
                   causal: bool):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0]                                      # [BQ, D] input dtype
    do = do_ref[0]
    lse = lse_ref[0, 0]                               # [BQ]
    delta = delta_ref[0, 0]                           # [BQ]
    n_kv = seq_len // block_k

    def body(ki, dq):
        # bf16 operands on the MXU, fp32 accumulation (see _fwd_kernel).
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                  # [BQ, BK] fp32
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jnp.dot(ds.astype(k_blk.dtype), k_blk,
                            preferred_element_type=jnp.float32)

    if causal:
        upper = jax.lax.min(
            ((qi + 1) * block_q - 1) // block_k + 1, n_kv)
    else:
        upper = n_kv
    dq0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    dq = jax.lax.fori_loop(0, upper, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                    block_k: int, seq_len: int, scale: float, causal: bool,
                    rep: int):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    r = pl.program_id(2)      # q head within this kv group (innermost dim:
    # the dk/dv output block index ignores r, so the accumulators stay
    # VMEM-resident across the whole group)
    k = k_ref[0]                                      # [BK, D] input dtype
    v = v_ref[0]
    n_q = seq_len // block_q

    @pl.when(r == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(qi, carry):
        dk, dv = carry
        # bf16 operands on the MXU, fp32 accumulation (see _fwd_kernel).
        q_blk = q_ref[0, pl.ds(qi * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qi * block_q, block_q), :]
        lse_blk = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        s = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_blk[:, None])              # [BQ, BK] fp32
        dv_new = dv + jnp.dot(p.astype(do_blk.dtype).T, do_blk,
                              preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None]) * scale
        dk_new = dk + jnp.dot(ds.astype(q_blk.dtype).T, q_blk,
                              preferred_element_type=jnp.float32)
        return dk_new, dv_new

    if causal:
        lower = (ki * block_k) // block_q             # first unmasked q block
    else:
        lower = 0
    dk, dv = jax.lax.fori_loop(lower, n_q, body, (dk_acc[...], dv_acc[...]))
    dk_acc[...] = dk
    dv_acc[...] = dv

    @pl.when(r == rep - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, scale, causal, block_q,
                    block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    KV, Dv = k.shape[2], v.shape[3]
    rep = H // KV
    qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    dot = _to_bhsd(g)
    # delta_i = rowsum(dO * O): cheap elementwise, done outside the kernels.
    delta = jnp.sum(dot.astype(jnp.float32) *
                    _to_bhsd(out).astype(jnp.float32), axis=-1)  # [BH, S]
    BH = B * H
    lse3 = jnp.broadcast_to(lse[:, None, :], (BH, 8, S))
    delta3 = jnp.broadcast_to(delta[:, None, :], (BH, 8, S))

    common_in = [qt, kt, vt, dot, lse3, delta3]
    kv_map = _kv_row_map(H, KV)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S, scale=scale, causal=causal),
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, D), kv_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, Dv), kv_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, Dv), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda bh, qi: (bh, 0, qi),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda bh, qi: (bh, 0, qi),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
        name="hvd_flash_bwd_dq",
        **_compiler_params(S, D, Dv, q.dtype.itemsize,
                           max(block_q, block_k)),
    )(*common_in)

    # dk/dv: one program per (kv row, k block, q-head-in-group), r
    # innermost so the fp32 scratch accumulators survive the whole group
    # in VMEM and flush once — exact fp32 accumulation over the rep q
    # heads without rep x VMEM for Q/dO (each r step re-indexes the
    # [1, S, D] Q/dO blocks instead of widening them).
    grp = lambda kb, ki, r: (kb * rep + r, 0, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S, scale=scale, causal=causal, rep=rep),
        grid=(B * KV, S // block_k, rep),
        in_specs=[
            pl.BlockSpec((1, S, D), grp, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda kb, ki, r: (kb, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, Dv), lambda kb, ki, r: (kb, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, Dv), grp, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, S), grp, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, S), grp, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda kb, ki, r: (kb, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, Dv), lambda kb, ki, r: (kb, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * KV, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * KV, S, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_flash_bwd_dkv",
        **_compiler_params(S, D, Dv, q.dtype.itemsize,
                           max(block_q, block_k)),
    )(*common_in)

    return (_from_bhsd(dq, B, H), _from_bhsd(dk, B, KV),
            _from_bhsd(dv, B, KV))


# ---------------------------------------------------------------------------
# paged decode kernel (serving: block-paged KV cache)
# ---------------------------------------------------------------------------

def _paged_decode_kernel(layer_ref, tables_ref, lengths_ref, q_ref, k_hbm,
                         v_hbm, o_ref, kbuf, vbuf, sem, first_slot, *,
                         scale: float):
    """One stream of paged decode attention: walk the stream's LIVE pages
    in groups of ``P``, online-softmax accumulating all q heads at once.

    The pools stay in HBM and the kernel fetches a group's pages itself
    (``make_async_copy``) into one of two VMEM slots, starting group
    ``g+1`` before it computes group ``g``; a stream's last group starts
    the first group of the next row that has a stream (the grid runs in
    order, and ``first_slot`` hands the slot on), so only the call's very
    first copies are waited for in the open.  Only live pages are copied:
    the loop's trip count is ``ceil(length / (P*BS))``, so a padded table
    slot is never visited and the table's width costs nothing; a stream's
    last group copies the ``ceil(length / BS) - g*P`` pages it holds and
    no more; a row of length 0 (a slot of the batch with no stream)
    starts no copy, waits for none, runs no product and reads as zeros.

    A group lands as ``[P*BS*KV, Dh]``: rows are (token, kv head), the
    pool's own order, so no transpose or relayout of a page is asked of
    Mosaic.  Both products run on the MXU over the whole group, with
    operands in the pool's dtype and float32 accumulation (the contract
    of :func:`_fwd_kernel`): ``q [H, Dh] . K^T -> [H, P*BS*KV]``, every
    column whose kv head is not the row's masked together with the
    columns past ``length``, and ``p . V -> [H, Dh]`` with ``p`` cast to
    V's dtype.  Masked entries are exact zeros, so the result is the GQA
    attention; rows of a slot that a short group's copies did not reach
    hold an earlier group's pages, or the zeros the call's first step
    fills V's slots with, so they are finite under ``p == 0`` (K's are
    replaced by the mask).  The KV-fold surplus of MXU work and ``exp``s
    is on units that otherwise idle while the pages arrive (on v5e the
    copies alone take 1.7 to 2.6 times the compute alone: PERF.md, PR
    28).

    Pools narrower than 32 bits move as uint32 words: a word holds the
    same column of two adjacent rows, the sublane packing of both the
    HBM tile and a vreg, so the group is loaded at full vreg width and
    reinterpreted, where a load in the pool's dtype would arrive in
    half-filled vregs and be repacked (2 ops a vreg, on every byte)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, Dh = q_ref.shape
    BS, KV = k_hbm.shape[2:4]
    rep = H // KV
    if kbuf.dtype != k_hbm.dtype:
        k_hbm, v_hbm = k_hbm.bitcast(kbuf.dtype), v_hbm.bitcast(kbuf.dtype)
    page_rows = k_hbm.shape[2] * k_hbm.shape[3]       # buffer rows a page
    P = kbuf.shape[1] // page_rows
    G = P * BS                                        # tokens a group
    b = pl.program_id(0)
    B = pl.num_programs(0)
    li = layer_ref[0]
    length = lengths_ref[b]
    n_groups = (length + G - 1) // G

    def pages_of(stream, g):
        """Live pages of ``stream``'s group ``g``: ``P`` but in its last."""
        return jnp.minimum((lengths_ref[stream] + BS - 1) // BS - g * P, P)

    def next_stream(row):
        """The first row from ``row`` on that has a stream, else ``B``."""
        return jax.lax.while_loop(
            lambda r: (r < B) & (lengths_ref[jnp.minimum(r, B - 1)] == 0),
            lambda r: r + 1, row)

    def start(stream, g, slot):
        # A run-time loop over the pages, not 2P descriptors written out:
        # the kernel is traced again at every table width, and written
        # out its three start sites cost a decode compile more host time
        # than the rest of the step (2 s of the backlog cell's set-up),
        # for no device time.
        def page(p, _):
            blk = tables_ref[stream, g * P + p]
            rows = pl.ds(pl.multiple_of(p * page_rows, page_rows),
                         page_rows)
            for i, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                pltpu.make_async_copy(
                    hbm.at[li, blk].reshape(page_rows, Dh),
                    buf.at[slot, rows], sem.at[i, slot]).start()
        jax.lax.fori_loop(0, pages_of(stream, g), page, None)

    def wait(g, slot):
        # A slot's copies of K (of V) signal one semaphore, which counts
        # bytes: the waits have to take exactly what ``start`` sent, and
        # do so by the binary digits of the page count, so a whole group
        # is still one wait and a short one at most log2(P) + 1.
        pages = pages_of(b, g)
        for digit in reversed(range(P.bit_length())):
            rows = pl.ds(0, page_rows << digit)

            @pl.when((pages >> digit) & 1 == 1)
            def _take():
                for i, buf in enumerate((kbuf, vbuf)):
                    pltpu.make_async_copy(buf.at[slot, rows],
                                          buf.at[slot, rows],
                                          sem.at[i, slot]).wait()

    @pl.when(b == 0)
    def _first():
        first_slot[0] = 0
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        head = next_stream(0)
        pl.when(head < B)(lambda: start(head, 0, 0))

    slot0 = first_slot[0]
    q = q_ref[...]
    # Column c of a group is token c // KV of it, for kv head c % KV: the
    # token where that head is the row's own, else one no length reaches.
    col = jax.lax.broadcasted_iota(jnp.int32, (H, G * KV), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (H, G * KV), 0)
    tok = jnp.where(col % KV == row // rep, col // KV,
                    jnp.iinfo(jnp.int32).max)

    def load(buf, slot):
        x = buf[slot]
        return x if x.dtype == q.dtype else pltpu.bitcast(x, q.dtype)

    def body(g, carry):
        m, l, acc = carry
        slot = (slot0 + g) % 2

        @pl.when(g + 1 < n_groups)
        def _more():
            start(b, g + 1, 1 - slot)

        @pl.when(g + 1 == n_groups)
        def _hand_over():
            nxt = next_stream(b + 1)
            pl.when(nxt < B)(lambda: start(nxt, 0, 1 - slot))

        wait(g, slot)
        k, v = load(kbuf, slot), load(vbuf, slot)     # [G*KV, Dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(tok < length - g * G, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, Dh), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_groups, body, (m0, l0, acc0))
    first_slot[0] = (slot0 + n_groups) % 2
    # length >= 1 leaves every row a live column in every group it
    # visits, so l > 0; a length of 0 visits none and reads as zeros.
    o_ref[...] = jnp.where(length > 0, acc / l, 0.0).astype(o_ref.dtype)


# What the paged decode kernel may keep resident in VMEM at once, by
# :func:`_paged_resident`, under the default scoped limit.
_VMEM_BUDGET = 12 << 20

# Tokens the paged decode kernel takes in one group where VMEM allows:
# of 64, 128, 256 and 512 the fastest at the backlog cell's shape on v5e
# (the sweep is in PERF.md, PR 28).  Larger groups make fewer loop turns;
# a stream's last group copies only the pages it holds but both products
# still run over the whole group, so a larger group also means more
# masked columns (half a group a stream): products over the live halves
# or quarters of a group alone were slower at both served shapes
# (PERF.md, PR 34).
_PAGED_GROUP_TOKENS = 256


def _paged_resident(pages: int, block_size: int, head_dim: int,
                    kv_heads: int, heads: int, itemsize: int) -> int:
    """VMEM bytes of the paged decode kernel at ``pages`` pages a group:
    K and V groups in two slots each, and the float32 score block
    ``[H, P*BS*KV]`` three times over (scores, ``p``, the token map)."""
    rows = pages * block_size * kv_heads
    return 4 * rows * head_dim * itemsize + 3 * heads * rows * 4


def paged_group_pages(block_size: int, head_dim: int, kv_heads: int,
                      heads: int, itemsize: int, n_cols: int) -> int:
    """Pages the paged decode kernel fetches and multiplies at once, from
    the shapes alone: :data:`_PAGED_GROUP_TOKENS` tokens' worth, no more
    than the table is wide, halved until the resident set fits."""
    pages = max(1, min(_PAGED_GROUP_TOKENS // block_size, n_cols))
    while pages > 1 and _paged_resident(
            pages, block_size, head_dim, kv_heads, heads,
            itemsize) > _VMEM_BUDGET:
        pages //= 2
    return pages


def paged_supported(block_size: int, head_dim: int, kv_heads: int,
                    heads: int, itemsize: int) -> bool:
    """Pool geometries the paged decode kernel compiles for (interpret
    mode runs any).  The kernel slices single pages out of the pool in
    HBM, which Mosaic takes only in whole tiles: ``head_dim`` a multiple
    of the 128 lanes, and a token's kv heads filling whole 32-bit
    sublane rows (an odd count of bf16 heads does not).  Ahead-of-time
    compiles for v5e accepted KV 2 to 32, H up to 64, BS 4 to 256, Dh
    128 and 256 in bf16, and KV 8 in float32; refused Dh 16 and 64 and
    bf16 at KV 1 and 3.  Then one page a group has to fit VMEM; larger
    groups are :func:`paged_group_pages`'s to choose."""
    return (head_dim % 128 == 0 and (kv_heads * itemsize) % 4 == 0
            and _paged_resident(1, block_size, head_dim, kv_heads, heads,
                                itemsize) <= _VMEM_BUDGET)


def paged_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                    scale: Optional[float] = None, interpret: bool = False):
    """Decode-step attention over a block-paged KV pool, GQA-native.

    q [B, H, Dh] (one token per request); k_pool/v_pool the WHOLE pools
    [L, num_blocks, block_size, KV, Dh] and ``layer`` the int32 scalar
    index of the layer to read — the kernel addresses pages inside the
    pool, where slicing a layer out first would hand the custom call a
    copy of that layer's pages every call; tables [B, n_cols] int32
    physical block ids (rows padded with the scratch block 0); lengths
    [B] — logical positions ``< lengths[b]`` are live, the rest masked;
    a row of length 0 has no stream: none of its table is read, and its
    result is zeros.

    Layer, table and lengths ride ``PrefetchScalarGridSpec``'s
    scalar-prefetch channel; the pools are handed over in HBM
    (``memory_space=pl.ANY``) and each of the ``B`` grid steps copies its
    stream's live pages in itself — no gathered ``[B, T, KV, Dh]`` copy
    ever lands in HBM (the XLA fallback in the serving engine
    materializes exactly that copy), and the time follows the live KV
    bytes, not the table's width or the batch's empty rows.  Returns
    [B, H, Dh].
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Dh = q.shape
    L, NB, BS, KV, _ = k_pool.shape
    if H % KV:
        raise ValueError(f"kv heads {KV} must divide q heads {H}")
    if scale is None:
        scale = 1.0 / float(np.sqrt(Dh))
    itemsize = k_pool.dtype.itemsize
    P = paged_group_pages(BS, Dh, KV, H, itemsize, tables.shape[1])
    # Sub-32-bit pools travel as uint32 words (see the kernel) wherever
    # the kv heads pair up into them.
    pack = 4 // itemsize if KV % (4 // itemsize) == 0 else 1
    buf = pltpu.VMEM((2, P * BS * KV // pack, Dh),
                     jnp.uint32 if pack > 1 else k_pool.dtype)

    q_spec = pl.BlockSpec((None, H, Dh), lambda b, li, tbl, ln: (b, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hvd_paged_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      lengths.astype(jnp.int32), q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# latent paged decode kernel (serving: one shared entry a token and layer)
# ---------------------------------------------------------------------------

# Tokens the latent decode kernel takes in one group.  A group's score
# block is [H, G] with no kv-head fold, so it can be twice the GQA
# kernel's at a quarter of its VMEM.
_MLA_GROUP_TOKENS = 512


def _mla_paged_decode_kernel(layer_ref, tables_ref, lengths_ref, q_ref,
                             pool_hbm, o_ref, buf, sem, first_slot, *,
                             scale: float, v_dim: int):
    """One stream of latent (MLA) paged decode attention, the key
    up-projection absorbed into the query: the stream's live pages are
    walked in groups of ``P`` as :func:`_paged_decode_kernel` walks them
    (two VMEM slots, group ``g+1`` and the next stream's first group
    started before group ``g`` is computed, a last group copying the pages
    it holds, a row of length 0 copying nothing and reading as zeros).

    A page is ``[BS, W]``: a token's row is its normed latent (the first
    ``v_dim`` columns), its rotated shared key, and padding up to whole
    lanes.  It is copied ONCE and used for both products: ``q [H, W] .
    page^T -> [H, G]`` over the whole row (the query's padding columns
    are zero) and ``p . page[:, :v_dim] -> [H, v_dim]``.  Every head
    reads the same rows, so there is no kv-head fold and no masked
    surplus beyond the last group's tail; the buffer's slots are zeroed
    at the call's first step, so rows no copy reached are finite under
    ``p == 0``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H = q_ref.shape[0]
    BS = pool_hbm.shape[2]
    P = buf.shape[1] // BS
    G = P * BS
    b = pl.program_id(0)
    B = pl.num_programs(0)
    li = layer_ref[0]
    length = lengths_ref[b]
    n_groups = (length + G - 1) // G

    def pages_of(stream, g):
        return jnp.minimum((lengths_ref[stream] + BS - 1) // BS - g * P, P)

    def next_stream(row):
        return jax.lax.while_loop(
            lambda r: (r < B) & (lengths_ref[jnp.minimum(r, B - 1)] == 0),
            lambda r: r + 1, row)

    def start(stream, g, slot):
        def page(p, _):
            blk = tables_ref[stream, g * P + p]
            rows = pl.ds(pl.multiple_of(p * BS, BS), BS)
            pltpu.make_async_copy(pool_hbm.at[li, blk], buf.at[slot, rows],
                                  sem.at[slot]).start()
        jax.lax.fori_loop(0, pages_of(stream, g), page, None)

    def wait(g, slot):
        # The semaphore counts bytes: take exactly what ``start`` sent,
        # by the binary digits of the page count.
        pages = pages_of(b, g)
        for digit in reversed(range(P.bit_length())):
            rows = pl.ds(0, BS << digit)

            @pl.when((pages >> digit) & 1 == 1)
            def _take():
                pltpu.make_async_copy(buf.at[slot, rows], buf.at[slot, rows],
                                      sem.at[slot]).wait()

    @pl.when(b == 0)
    def _first():
        first_slot[0] = 0
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        head = next_stream(0)
        pl.when(head < B)(lambda: start(head, 0, 0))

    slot0 = first_slot[0]
    q = q_ref[...]
    tok = jax.lax.broadcasted_iota(jnp.int32, (H, G), 1)

    def body(g, carry):
        m, l, acc = carry
        slot = (slot0 + g) % 2

        @pl.when(g + 1 < n_groups)
        def _more():
            start(b, g + 1, 1 - slot)

        @pl.when(g + 1 == n_groups)
        def _hand_over():
            nxt = next_stream(b + 1)
            pl.when(nxt < B)(lambda: start(nxt, 0, 1 - slot))

        wait(g, slot)
        page = buf[slot]                                  # [G, W]
        s = jax.lax.dot_general(
            q, page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(tok < length - g * G, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(page.dtype), page[:, :v_dim],
                     preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, v_dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_groups, body, (m0, l0, acc0))
    first_slot[0] = (slot0 + n_groups) % 2
    o_ref[...] = jnp.where(length > 0, acc / l, 0.0).astype(o_ref.dtype)


def _mla_resident(pages: int, block_size: int, width: int, heads: int,
                  itemsize: int) -> int:
    """VMEM bytes of the latent decode kernel at ``pages`` pages a group:
    the group in two slots, the float32 score block three times over."""
    rows = pages * block_size
    return 2 * rows * width * itemsize + 3 * heads * rows * 4


def mla_group_pages(block_size: int, width: int, heads: int, itemsize: int,
                    n_cols: int) -> int:
    """Pages the latent decode kernel takes at once: a power of two (the
    waits go by binary digits), :data:`_MLA_GROUP_TOKENS` tokens' worth,
    no more than the table is wide, halved until the resident set fits."""
    pages = 1
    while pages * 2 <= min(max(1, _MLA_GROUP_TOKENS // block_size), n_cols):
        pages *= 2
    while pages > 1 and _mla_resident(pages, block_size, width, heads,
                                      itemsize) > _VMEM_BUDGET:
        pages //= 2
    return pages


def mla_paged_supported(block_size: int, width: int, heads: int,
                        itemsize: int) -> bool:
    """Latent pool geometries :func:`mla_paged_attention` compiles for
    (interpret mode runs any): a page ``[block_size, width]`` is sliced
    out of the pool in HBM whole, so its rows fill whole sublane tiles (16
    rows of bf16, 8 of float32) and its width whole 128-lane tiles, which
    the published 576 (latent 512, rope key 64) does not: the pool pads a
    token's row to 640.  Compiled ahead of time for a v5e at block sizes
    16 to 64, width 640, 20 heads, bf16 (PERF.md, PR 35)."""
    return (width % 128 == 0 and block_size % (32 // itemsize) == 0
            and _mla_resident(1, block_size, width, heads, itemsize)
            <= _VMEM_BUDGET)


def mla_paged_attention(q, pool, layer, tables, lengths, *, v_dim: int,
                        scale: float, interpret: bool = False):
    """Decode-step latent attention over a block-paged pool.

    q [B, H, W]: one token a request, every head's query in the pool's
    row space (the key up-projection absorbed: ``q_nope W_uk^T`` over the
    latent columns, the rotated ``q_pe`` over the rope-key columns, zeros
    over the padding); pool the WHOLE pool [L, num_blocks, block_size, W]
    and ``layer`` the int32 scalar index of the layer to read; tables
    [B, n_cols] int32 (rows padded with the scratch block 0); lengths
    [B]: positions ``< lengths[b]`` are live, a row of length 0 has no
    stream and reads as zeros.  Returns ``softmax(q . row) . row[:v_dim]``,
    [B, H, v_dim]: the caller takes it through the value up-projection."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    L, NB, BS, _ = pool.shape
    itemsize = pool.dtype.itemsize
    # whole sublane tiles of query heads (20 heads of bf16 become 32)
    sub = 32 // itemsize
    Hp = -(-H // sub) * sub
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    P = mla_group_pages(BS, W, Hp, itemsize, tables.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, Hp, W),
                               lambda b, li, tbl, ln: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, Hp, v_dim),
                               lambda b, li, tbl, ln: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, P * BS, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_mla_paged_decode_kernel, scale=scale,
                          v_dim=v_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hvd_mla_paged_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      lengths.astype(jnp.int32), q, pool)
    return out[:, :H]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def default_blocks(seq_len: int) -> tuple[int, int]:
    """Per-length (bq, bk) from the round-4 fwd+bwd sweeps on TPU v5 lite
    over the full bq×bk grid (``flash_block_sweep_r4`` records in
    benchmarks/measured.jsonl; B=4 H=16 D=64 bf16 causal, vs XLA dense).
    Measured AFTER the bf16-MXU kernel fix (operands stay bf16, fp32
    accumulation — the fp32-cast version ran the matmuls at fp32 MXU
    rate and its optimum differed):

        S=512:  (256, 256) → 1.01x (parity; decision in BASELINE.md —
                all S=512 blockings sit within noise of dense, and the
                committed sweep's fastest point is 256×256)
        S=1024: (512, 512) → 2.42 ms, 1.82x
        S=2048: (512, 512) → 4.79 ms, 2.54x
        S=4096: (512, 512) → 12.4 ms, 5.28x
    """
    if seq_len == 512:
        # Kept on the flash path at parity (≥1x) rather than gated to
        # dense: one uniform code path across lengths, and the smaller
        # resident set leaves VMEM headroom.  See "S=512 flash decision"
        # in BASELINE.md (round 6).
        return 256, 256
    if seq_len % 512 == 0:
        return 512, 512
    b = next((c for c in (256, 128) if seq_len % c == 0), 128)
    return b, b  # two-tuple API: callers may still override bq/bk apart


def supported(q_shape: tuple, itemsize: int = 4,
              v_dim: Optional[int] = None) -> bool:
    """Shapes the kernels compile for: seq divisible by a block size and
    the heaviest kernel's resident set (:func:`_flash_resident`; the
    value width ``v_dim`` is ``D`` unless given) within
    :data:`_FLASH_VMEM_BUDGET`."""
    B, S, H, D = q_shape
    bq, bk = default_blocks(S)
    return (S % bq == 0 and S % bk == 0 and S >= bq
            and _flash_resident(S, D, v_dim or D, itemsize, max(bq, bk))
            <= _FLASH_VMEM_BUDGET)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, scale: Optional[float] = None,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None, interpret: bool = False):
    """Exact attention, flash-style.  q: [B, S, H, D] → [B, S, H, Dv].

    The value width may differ from the key width (q, k ``[.., D]``, v
    and the output ``[.., Dv]``: latent attention trains with keys of
    nope + rope width and narrower values); the default scale is
    ``D ** -0.5``.

    GQA-native: k/v may carry ``KV = H / rep`` heads ([B, S, KV, D]) and
    are indexed per-group inside the kernels — K/V HBM arrays, traffic
    and dk/dv outputs all stay ``rep`` x smaller than a
    ``jnp.repeat``-expanded call (round-4 verdict ask #1a)."""
    out, _ = _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _resolve(q, scale, block_q, block_k):
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    dbq, dbk = default_blocks(q.shape[1])
    return scale, block_q or dbq, block_k or dbk


def _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret):
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(
            f"kv heads {k.shape[2]}/{v.shape[2]} must be equal and divide "
            f"q heads {q.shape[2]}")
    scale, bq, bk = _resolve(q, scale, block_q, block_k)
    return _flash_forward(q, k, v, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, interpret=interpret)


def _fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _bwd_rule(scale, causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    scale, bq, bk = _resolve(q, scale, block_q, block_k)
    return _flash_backward(q, k, v, out, lse, g, scale=scale, causal=causal,
                           block_q=bq, block_k=bk, interpret=interpret)


flash_attention.defvjp(_fwd_rule, _bwd_rule)

# Back-compat private name (tests and older callers).
_dense_attention = dense_attention
