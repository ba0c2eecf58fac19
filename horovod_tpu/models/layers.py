"""The decoder block, written once, and the pieces every decoder shares.

A decoder layer is :func:`block`: ``h + mixer(norm(h))`` then ``h +
mlp(norm(h))``, each branch normed once more before it is added where the
layer has the gains for it (sandwich norm).  A stack whose weights are
used several times over is :func:`looped`.  A layer *kind* is a ``(mixer,
mlp)`` pair of callables, each ``(x, lp) -> (y, extra)``; what differs
between training, prefill and a paged decode tick of one model is passed
in, never branched on:

- the **mixer** (:func:`gqa_mixer` for the Llama family,
  :func:`mla_mixer` for latent attention; Kimi-Linear brings its KDA
  mixer) takes an ``attend``
  closure that is handed rotated q and grouped k, v and returns the
  attention output and whatever the caller's scan must carry out: the
  new K and V, the updated pools, or nothing;
- the **mlp** is :func:`dense_mlp` or a model's expert layer, its
  ``extra`` the auxiliary loss or routing counts.

Beside the frame live the pieces both model files use: RMSNorm with its
hand-written VJP, RoPE, the embedding lookup, the SwiGLU MLP, the remat
modes, the attention dispatch (sp / flash / dense), the cache-side
attention and block gather of the serving path, and the causal-LM loss.
Nothing here imports a model, ``serving`` or ``context``.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.trace import region
from ..ops import flash_attention as FA
from ..parallel.ring_attention import (
    ring_attention_local,
    ulysses_attention_local,
)
from ..utils import logging as hvd_logging

log = hvd_logging.get_logger()

# Logical dims of the serving page pool [L, NB, BS, KV, Dh].
POOL_DIMS = (None, None, None, "kv_heads", None)


def remat(body, mode):
    """Apply the configured rematerialization mode to a layer body."""
    if mode == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body) if mode else body


def _rmsnorm_impl(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * w).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with a hand-written VJP whose only residual is ``x``.

    Autodiff of the plain version makes XLA save the fp32 normalized
    activations for the backward: two f32[B,S,D] tensors per layer riding
    the layer-scan carry through HBM.  The backward recomputes the rsqrt
    from the already-saved ``x`` instead, a handful of VPU ops."""
    return _rmsnorm_impl(x, w, eps)


def _rmsnorm_fwd(x, w, eps):
    return _rmsnorm_impl(x, w, eps), (x, w)


def _rmsnorm_bwd(eps, res, dy):
    x, w = res
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    u = x32 * r                                   # normalized activations
    du = dy.astype(jnp.float32) * w               # d(loss)/d(u)
    s = jnp.mean(du * u, axis=-1, keepdims=True)
    dx = (r * (du - u * s)).astype(x.dtype)
    dw = jnp.sum(dy.astype(jnp.float32) * u,
                 axis=tuple(range(x.ndim - 1))).astype(w.dtype)
    return dx, dw


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rope_tables(positions: jax.Array, theta: float, head_dim: int
                ) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [B, S, half] for these positions.  Computed once per
    forward and threaded through the layer scan as loop invariants rather
    than re-deriving the transcendentals per layer."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    return jnp.cos(angles), jnp.sin(angles)


def rope(x: jax.Array, tables: tuple[jax.Array, jax.Array]) -> jax.Array:
    # x: [B, S, H, Dh]; tables: (cos, sin) each [B, S, Dh//2]
    half = x.shape[-1] // 2
    cos, sin = tables[0][:, :, None, :], tables[1][:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def embed_lookup(embed: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    """Token embedding as a one-hot matmul rather than a gather: exact
    (each one-hot row has a single nonzero), the backward is a transposed
    matmul on the MXU instead of a scatter-add, and it partitions cleanly
    under the vocab_rows (tp, fsdp) sharding — a sharded gather lowers
    to per-shard lookup + select + psum anyway."""
    with region("embed"):
        onehot = jax.nn.one_hot(tokens, embed.shape[0], dtype=dtype)
        return jnp.einsum("bsv,vd->bsd", onehot, embed.astype(dtype))


def dense_mlp(x2, lp):
    """SwiGLU MLP, in the frame's mlp form: ``(out, None)``."""
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x2, lp["w_gate"]))
    u = jnp.einsum("bsd,df->bsf", x2, lp["w_up"])
    return jnp.einsum("bsf,fd->bsd", g * u, lp["w_down"]), None


# -- the frame ----------------------------------------------------------------

def block(h, lp, mixer, mlp, eps: float = 1e-5):
    """The pre-norm decoder block: ``h + mixer(norm(h))``, then ``h +
    mlp(norm(h))``.  ``mixer`` and ``mlp`` are ``(x, lp) -> (y, extra)``;
    returns ``(h, the mixer's extra, the mlp's extra)``.  A layer that
    holds ``attn_post_norm`` and ``mlp_post_norm`` (sandwich norm) norms
    each branch's output with them before the residual takes it.  The
    two halves are the regions ``hvd.block.mixer`` and ``hvd.block.mlp``
    of whatever program runs the layer."""
    with region("block.mixer"):
        y, kept = mixer(rmsnorm(h, lp["attn_norm"], eps), lp)
        if "attn_post_norm" in lp:
            y = rmsnorm(y, lp["attn_post_norm"], eps)
        h = h + y
    with region("block.mlp"):
        y, aux = mlp(rmsnorm(h, lp["mlp_norm"], eps), lp)
        if "mlp_post_norm" in lp:
            y = rmsnorm(y, lp["mlp_post_norm"], eps)
        return h + y, kept, aux


def looped(layer, carry, stacked, loops: int, renorm, xs=None,
           unroll: int = 1):
    """The scan of a stack run ``loops`` times over the same weights (a
    looped transformer).  ``layer((h, rest), (lp, x)) -> ((h, rest),
    out)`` is one layer; ``stacked`` holds the layers' leaves on a leading
    axis of ``L``, ``xs`` what differs by pass and layer (leading axis
    ``loops * L``: step ``t * L + l`` is layer ``l`` of pass ``t``), the
    ``out`` leaves come back stacked as deep.  Every pass after the first
    starts from ``renorm`` of what the pass before it left, so with the
    head's own norm after the last, each pass ends normed.  One loop over
    all steps, the layer picked by index: the weights are each step's to
    read where they lie, as in the plain decoder's scan, which is
    ``loops == 1``."""
    if loops == 1:
        return jax.lax.scan(layer, carry, (stacked, xs), unroll=unroll)
    L = jax.tree.leaves(stacked)[0].shape[0]

    def step(carry, ix):
        i, x = ix
        h, rest = carry
        with region("renorm"):
            h = jax.lax.cond((i % L == 0) & (i > 0), renorm, lambda h: h, h)
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i % L, keepdims=False), stacked)
        return layer((h, rest), (lp, x))

    return jax.lax.scan(step, carry, (jnp.arange(loops * L), xs),
                        unroll=unroll)


def gqa_mixer(x, lp, tables, attend):
    """The Llama mixer: project q, k, v, rotate q and k by the rope
    ``tables``, ``attend(q, k, v) -> (o, kept)`` on GROUPED k, v (each
    path expands only if it must), project with ``wo``."""
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
    q = rope(q, tables)
    k = rope(k, tables)
    o, kept = attend(q, k, v)
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), kept


def mla_mixer(x, lp, nope: int, eps: float, tables, attend):
    """The latent-attention mixer (MLA), one function for every model
    that has the layer: queries full-rank (``wq``) or compressed and
    normed (``w_qa``, ``q_norm``, ``w_qb``), by the leaves the layer
    holds; the normed latent ``c [B, S, C]`` and the one key of ``rope``
    columns all heads share, ``k_pe [B, S, R]``; the last ``R`` columns
    of a query head and ``k_pe`` rotated by the rope ``tables`` (None: no
    position, as Kimi-Linear's).  ``attend(q, c, k_pe, w_kvb) -> (o,
    kept)`` is the caller's, as :func:`gqa_mixer`'s: expanded keys and
    values (:func:`mla_expand`) for training and prefill, the query
    taken into the latent's space (:func:`mla_absorb`) against a cache of
    ``(c, k_pe)`` rows for a decode tick."""
    C = lp["kv_norm"].shape[-1]
    if "w_qa" in lp:
        cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, lp["w_qa"]),
                     lp["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, lp["w_qb"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
    kva = jnp.einsum("bsd,dc->bsc", x, lp["w_kva"])
    c = rmsnorm(kva[..., :C], lp["kv_norm"], eps)
    k_pe = kva[..., C:]
    if tables is not None:
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], tables)], -1)
        k_pe = rope(k_pe[:, :, None, :], tables)[:, :, 0]
    o, kept = attend(q, c, k_pe, lp["w_kvb"])
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), kept


def mla_expand(c, k_pe, w_kvb, nope: int):
    """Every head's keys ``[B, S, H, nope + R]`` and values from the
    latent ``c`` and the shared rope key: the expanded form of latent
    attention."""
    kv = jnp.einsum("bsc,chk->bshk", c, w_kvb)
    B, S, H, _ = kv.shape
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, k_pe.shape[-1]))
    return jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]


def mla_expanded_attend(nope: int, mesh):
    """The ``attend`` of :func:`mla_mixer` in the expanded form, for a
    whole sequence (training, the tests' forward): every head's keys and
    values through the attention dispatch (the flash kernels)."""
    def attend(q, c, k_pe, w_kvb):
        k, v = mla_expand(c, k_pe, w_kvb, nope)
        return attention(q, k, v, mesh, True), None
    return attend


def mla_row(c, k_pe, width: int):
    """A token's cache row ``[..., width]``: latent, rope key, zeros."""
    row = jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)
    pad = width - row.shape[-1]
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)]) if pad \
        else row


def mla_absorb(q, w_kvb, nope: int, width: int):
    """The query in the cache row's space (the absorbed form): ``q_nope
    W_uk^T`` over the latent's columns, ``q_pe`` over the rope key's, so
    that ``q_row . row == q . k`` of the expanded form."""
    q_lat = jnp.einsum("bshn,chn->bshc", q[..., :nope], w_kvb[..., :nope])
    return mla_row(q_lat, q[..., nope:], width)


# -- attention ----------------------------------------------------------------

# Test hook: route the TPU-gated flash branches through the Pallas
# interpreter so the CPU rig can exercise the exact structures the TPU
# path uses (the dp/fsdp/tp shard_map in `attention` and the direct
# kernel call inside the fully-manual pipeline region).
_FORCE_FLASH_INTERPRET = False


def _flash_backend() -> bool:
    return jax.default_backend() == "tpu" or _FORCE_FLASH_INTERPRET


@lru_cache(maxsize=None)
def _log_attention_path(path: str, q_shape: tuple, mesh_shape) -> None:
    """INFO line naming the attention implementation a traced step uses,
    once per distinct (path, local shape, mesh)."""
    log.info("llama attention path: %s (local q %s, mesh %s)", path,
             q_shape, dict(mesh_shape) if mesh_shape else None)


def sp_local_attention(sp_mode: str):
    """The mapped-context sequence-parallel attention for ``sp_mode``."""
    if sp_mode == "ulysses":
        return ulysses_attention_local
    if sp_mode == "ring":
        return ring_attention_local
    raise ValueError(f"unknown sp_attention {sp_mode!r} "
                     "(expected 'ring' or 'ulysses')")


def attention_path(q_shape: tuple, itemsize: int, mesh: Optional[Mesh],
                   sp_mode: str = "ring", v_dim: Optional[int] = None
                   ) -> str:
    """Which implementation :func:`attention` runs for a global
    ``[B, S, H, D]`` query on ``mesh``: ``"ring"``/``"ulysses"`` when the
    sequence is sp-sharded, ``"flash"`` (the Pallas kernels) on TPU when
    the per-chip shard divides evenly and :func:`FA.supported` accepts
    it, ``"dense"`` (XLA) otherwise.  ``v_dim`` is the value width where
    it differs from the key width ``D``."""
    shape = dict(mesh.shape) if mesh is not None else {}
    if shape.get("sp", 1) > 1:
        sp_local_attention(sp_mode)
        return sp_mode
    B, S, H, D = q_shape
    dpf = shape.get("dp", 1) * shape.get("fsdp", 1)
    tp = shape.get("tp", 1)
    if (_flash_backend() and B % dpf == 0 and H % tp == 0
            and FA.supported((B // dpf, S, H // tp, D), itemsize, v_dim)):
        return "flash"
    return "dense"


def attention(q, k, v, mesh: Optional[Mesh], causal: bool,
              sp_mode: str = "ring") -> jax.Array:
    """Dispatch per :func:`attention_path`.  Under a mesh the sp paths and
    the flash kernel are shard_mapped so each chip works on its own
    batch/head shard (a bare pallas_call has no GSPMD partitioning rule
    and would be replicated)."""
    path = attention_path(q.shape, q.dtype.itemsize, mesh, sp_mode,
                          v.shape[-1])
    _log_attention_path(path, q.shape,
                        tuple(mesh.shape.items()) if mesh is not None
                        else None)
    if path in ("ring", "ulysses"):
        k, v = FA.gqa_expand(q, k, v)   # ring/Ulysses rotate full head sets
        # Manual over every mesh axis: the batch/head dims are explicitly
        # dp·fsdp / tp sliced instead of left to GSPMD, and the body only
        # communicates over sp.
        spec = P(("dp", "fsdp"), "sp", "tp", None)
        fn = shard_map(
            partial(sp_local_attention(sp_mode), axis_name="sp",
                    causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False)
        return fn(q, k, v)
    if path == "flash":
        flash = lambda q_, k_, v_: FA.flash_attention(
            q_, k_, v_, None, causal, None, None, _FORCE_FLASH_INTERPRET)
        if mesh is None:
            return flash(q, k, v)
        if k.shape[2] % mesh.shape.get("tp", 1):
            # tp divides H but not KV: the grouped cache cannot shard
            # over tp — expand K/V and keep the flash kernel (losing it
            # entirely would be a 2-5x regression for the sake of the
            # GQA memory win).
            k, v = FA.gqa_expand(q, k, v)
        spec = P(("dp", "fsdp"), None, "tp", None)
        return shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
    return FA.dense_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1]), causal)


def cached_attend(q, keys, vals, mask, scale):
    """Decode-path attention against a KV cache, GQA-grouped.

    q [B,Sq,H,Dh]; keys [B,T,KV,Dh], vals [B,T,KV,Dv]; mask [Sq,T] bool (shared across
    the batch) or [B,Sq,T] (per-request — the serving engine's slots sit
    at different context lengths).  The q heads are reshaped [KV, rep]
    and contracted against the grouped cache directly — the cache is
    never expanded to H heads (the repeat would rep x the dominant HBM
    traffic of decoding, which is exactly reading the cache)."""
    B, Sq, H, Dh = q.shape
    KV = keys.shape[2]
    rep = H // KV
    qg = q.reshape(B, Sq, KV, rep, Dh)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, keys
                   ).astype(jnp.float32) * scale
    m = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(vals.dtype), vals)
    return o.reshape(B, Sq, H, vals.shape[-1])


def gather_blocks(pool, table) -> jax.Array:
    """Contiguous ``[B, n_cols * block_size, KV, D]`` view of each row's
    blocks: the XLA paged-attention dispatch (a take along the block dim,
    shardable by GSPMD like any gather).

    pool: ``[num_blocks, block_size, KV, D]`` (one layer's pages);
    table: ``[B, n_cols]`` int32.
    """
    B, n_cols = table.shape
    g = pool[table]                       # [B, n_cols, BS, KV, D]
    return g.reshape(B, n_cols * pool.shape[1], *pool.shape[2:])


# -- the loss -----------------------------------------------------------------

def causal_lm_loss(forward, lm_head, tokens, blockwise: bool):
    """Mean next-token cross-entropy of ``tokens [B, S+1]`` and whatever
    rides beside the forward's first output.  ``forward(inputs,
    return_hidden)`` gives float32 logits, or with ``return_hidden`` the
    final normed hidden states, which the blockwise form (ops/losses.py)
    takes to ``lm_head`` itself, vocab block by vocab block."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if blockwise:
        from ..ops.losses import blockwise_cross_entropy
        h, extra = forward(inputs, True)
        with region("loss"):       # the head's product is in the blocks
            nll = blockwise_cross_entropy(
                h.reshape(-1, h.shape[-1]), lm_head,
                targets.reshape(-1).astype(jnp.int32))
            return nll.mean(), extra
    logits, extra = forward(inputs, False)
    # logsumexp form of the CE — identical math to log_softmax + gather,
    # but the [B,S,V] fp32 log-prob tensor is never materialized, only
    # its row reduction.
    with region("loss"):
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]
        return (lse - picked).mean(), extra
