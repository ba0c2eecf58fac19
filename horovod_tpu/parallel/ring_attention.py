"""Ring attention: exact attention over sequences sharded across devices.

Sequence/context parallelism is ABSENT in the reference (SURVEY §2.6) — this
is capability-beyond-parity required for the Llama long-context config.

Algorithm (Liu, Zaharia & Abbeel, "Ring Attention with Blockwise
Transformers", arXiv:2310.01889): the sequence is chunked contiguously
across the ``sp`` mesh axis; Q stays resident while K/V blocks rotate
around the ICI ring via ``ppermute``.  Each hop contributes one block of
scores folded in with online (flash-style) softmax accumulation, so memory
stays O(local_seq²) and the N-1 rotations overlap with block compute —
XLA schedules the ``collective-permute`` concurrently with the matmuls,
which is what makes the ring bandwidth-optimal on the torus.

Causality on the ring: rank *i* owns tokens ``[i*C, (i+1)*C)``.  After *s*
hops the resident KV block originated at rank ``(i - s) mod n``:
- origin < i   → fully visible,
- origin == i  → lower-triangular block mask,
- origin > i   → fully masked (contributes nothing, but the hop still
  happens so every rank stays in lockstep — same reason the reference's
  coordinator keeps collective order identical on all ranks).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attend(q, k, v, scale, mask):
    """Scores for one (local-Q × resident-KV) block.

    q: [B, Lq, H, D]; k/v: [B, Lk, H, D]; mask: [Lq, Lk] bool or None.
    Returns (scores [B, H, Lq, Lk]) pre-softmax, masked.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, _NEG_INF)
    return s


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         axis_name: str = "sp",
                         causal: bool = True,
                         scale: Optional[float] = None) -> jax.Array:
    """Exact attention for locally-sharded q/k/v inside a mapped context.

    Shapes (local shard): ``q,k,v: [batch, local_seq, heads, head_dim]``;
    returns the same shape.  Call inside ``shard_map``/``pjit``-mapped code
    whose ``axis_name`` axis shards the sequence dimension.
    """
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, L, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    # Online-softmax accumulators.
    m = jnp.full((B, H, L), _NEG_INF, jnp.float32)          # running max
    l = jnp.zeros((B, H, L), jnp.float32)                   # running denom
    o = jnp.zeros((B, L, H, D), jnp.float32)                # running numer

    perm = [(i, (i + 1) % n) for i in range(n)]
    tri = jnp.tril(jnp.ones((L, L), bool)) if causal else None

    def fold(carry, kv_origin, k_blk, v_blk):
        m_, l_, o_ = carry
        if causal:
            # Block-level causal visibility (see module docstring).
            full = kv_origin < my
            diag = kv_origin == my
            base = jnp.where(full, True, False)
            mask = jnp.where(diag, tri, jnp.broadcast_to(base, (L, L)))
        else:
            mask = None
        s = _block_attend(q, k_blk, v_blk, scale, mask).astype(jnp.float32)
        blk_max = s.max(axis=-1)                            # [B,H,L]
        m_new = jnp.maximum(m_, blk_max)
        alpha = jnp.exp(m_ - m_new)
        p = jnp.exp(s - m_new[..., None])                   # [B,H,Lq,Lk]
        l_new = l_ * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        o_new = o_ * alpha.transpose(0, 2, 1)[..., None] + pv
        return m_new, l_new, o_new

    carry = (m, l, o)
    k_cur, v_cur = k, v
    for step in range(n):
        origin = (my - step) % n
        carry = fold(carry, origin, k_cur, v_cur)
        if step != n - 1:
            # Rotate KV to the next rank; XLA overlaps this collective-
            # permute with the next block's matmuls.
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    m_, l_, o_ = carry
    out = o_ / jnp.maximum(l_, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        mesh: Mesh, *, axis_name: str = "sp",
                        causal: bool = True,
                        scale: Optional[float] = None) -> jax.Array:
    """Standalone entry: q/k/v are global ``[B, S, H, D]`` arrays; the
    sequence dim is sharded over ``axis_name`` and exact attention is
    computed with the ring schedule."""
    fn = shard_map(
        partial(ring_attention_local, axis_name=axis_name, causal=causal,
                scale=scale),
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False)
    return jax.jit(fn)(q, k, v)


def ulysses_attention_local(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            axis_name: str = "sp",
                            causal: bool = True,
                            scale: Optional[float] = None) -> jax.Array:
    """Ulysses-style sequence parallelism (DeepSpeed-Ulysses,
    arXiv:2309.14509): all_to_all swaps the sharded dim from sequence to
    heads, runs full-sequence attention on 1/n of the heads, and swaps back.
    Uses the same alltoall primitive the collective layer must provide
    anyway (SURVEY §5.7); preferable when heads % n == 0 and sequence fits.
    """
    n = axis_size(axis_name)
    B, L, H, D = q.shape
    if H % n:
        raise ValueError(
            f"sp size ({n}) must divide heads ({H}) for Ulysses")

    # tiled=True all_to_alls: split_axis chunked across the axis, concat
    # axis grown n-fold, no intermediate block reshapes.  (The tiled=False
    # block formulation had a broken transpose on this jax — the vjp's
    # cotangent came back mis-shaped when split_axis != concat_axis, which
    # only surfaced once the model grew a differentiated Ulysses path.)
    def seq_to_heads(x):
        # [B, L, H, D] local-seq → [B, n*L, H/n, D] local-heads
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    S = qh.shape[1]
    mask = jnp.tril(jnp.ones((S, S), bool)) if causal else None
    s = _block_attend(qh, kh, vh, scale, mask).astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return heads_to_seq(out.astype(q.dtype))
