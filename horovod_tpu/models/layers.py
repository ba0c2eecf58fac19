"""The decoder block, written once, and the pieces every decoder shares.

A decoder layer is :func:`block`: ``h + mixer(norm(h))`` then ``h +
mlp(norm(h))``, each branch normed once more before it is added where the
layer has the gains for it (sandwich norm).  A layer whose residual is
several streams mixed by per-token maps (manifold-constrained
hyper-connections) goes through :func:`hc_block` instead, with the same
``(mixer, mlp)`` pair.  A stack whose weights are used several times
over is :func:`looped`.  A layer *kind* is a ``(mixer,
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

Beside the frame live the pieces the model files share: RMSNorm with its
hand-written VJP, RoPE, the embedding lookup, the SwiGLU MLP, the remat
modes, the attention dispatch (sp / flash / dense), the cache-side
attention and block gather of the serving path, the GQA mixer's paged
cache (what every model with :func:`gqa_mixer` gives the serving steps),
the head and the causal-LM loss.  Nothing here imports a model,
``serving`` or ``context``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import REGISTRY as _obs
from ..obs.trace import region
from ..ops import flash_attention as FA
from ..parallel import sharding as shd
from ..parallel.ring_attention import (
    ring_attention_local,
    ulysses_attention_local,
)
from ..utils import logging as hvd_logging

log = hvd_logging.get_logger()

# Logical dims of the serving page pool [L, NB, BS, KV, Dh].
POOL_DIMS = (None, None, None, "kv_heads", None)


# The residuals a training step names (``checkpoint_name``; the flash
# kernel's forward rule names its two), in the groups
# :func:`saved_residuals` keeps whole, most recompute saved a byte first:
# the mixer's (q and k after rope, v, the flash output and its row sums,
# the mixer's output: nothing of it but its norm runs again), then the
# MLP's (the gate and up products).  Outside a ``jax.checkpoint`` with a
# names policy a name lowers to nothing.
MIXER_RESIDUALS = ("attn_q", "attn_k", "attn_v", "flash_out", "flash_lse",
                   "attn_out")
MLP_RESIDUALS = ("mlp_gate", "mlp_up")

# What the step's temporaries grow by, a byte of residual saved over the
# layers (the Mistral-7B train step compiled for a v5e: 1.97 for the
# mixer's, 2.02 for the MLP's), and what the caller's estimate of the step
# must leave free beside them.
SAVED_GROWTH = 2
SAVED_MARGIN = 1e9


def saved_residuals(groups, free_bytes: float) -> tuple:
    """The names a remat'd layer keeps: whole ``groups`` of ``(names,
    bytes over all layers)``, in order, while ``SAVED_GROWTH`` times the
    bytes taken fit in ``free_bytes`` (the device's memory less the
    step's own estimate) less ``SAVED_MARGIN``."""
    names, spent = (), 0
    for group, nbytes in groups:
        spent += SAVED_GROWTH * nbytes
        if spent > free_bytes - SAVED_MARGIN:
            break
        names += tuple(group)
    return names


def device_bytes() -> Optional[int]:
    """The memory the first local device lends a program
    (``bytes_limit``); None where the backend does not say (the CPU)."""
    return (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")


_m_saved = _obs.gauge(
    "hvd_train_remat_saved_bytes",
    "bytes of a named residual that the remat'd layer stack keeps for the "
    "backward instead of recomputing it, all layers; set when a step is "
    "traced", ("name",))


def record_saved(saved: dict) -> None:
    """Trace time: ``hvd_train_remat_saved_bytes`` for each residual of
    ``saved`` (``{name: bytes over all layers}``), and one log line for
    each set a step keeps."""
    for name, nbytes in saved.items():
        _m_saved.labels(name=name).set(nbytes)
    if saved:
        _log_saved(tuple(saved.items()))


@lru_cache(maxsize=None)
def _log_saved(saved: tuple) -> None:
    log.info("remat keeps %s for the backward (%.3f GB over the layers)",
             ", ".join(n for n, _ in saved), sum(b for _, b in saved) / 1e9)


def remat(body, mode, saved: tuple = ()):
    """Apply the configured rematerialization mode to a layer body:
    True recomputes the layer in the backward but for the named
    residuals ``saved`` (none: plain ``jax.checkpoint``), ``"dots"``
    saves the matmul outputs, False nothing is recomputed."""
    if mode == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if mode and saved:
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                *saved))
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


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's scaled rotary embedding (arXiv:2309.00071) in DeepSeek-V3's
    form, the published ``rope_scaling`` of ``type: yarn``: the
    frequencies that turn fewer than ``beta_slow`` times over the
    original context are divided by ``factor``, those that turn more than
    ``beta_fast`` times are kept, a linear ramp between; the softmax scale
    gains ``mscale(factor, mscale_all_dim)**2``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def correction_range(self, theta: float, dim: int) -> tuple:
        """The first and last frequency index of the ramp (floor and
        ceil of the dimension at which ``beta`` turns fit the original
        context)."""
        at = lambda turns: dim * math.log(
            self.original_max_position_embeddings / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))
        return (max(math.floor(at(self.beta_fast)), 0),
                min(math.ceil(at(self.beta_slow)), dim - 1))

    def inv_freq(self, theta: float, dim: int) -> np.ndarray:
        """The ``dim // 2`` rotary frequencies, float64."""
        base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        lo, hi = self.correction_range(theta, dim)
        ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3),
                       0.0, 1.0)
        return base / self.factor * ramp + base * (1.0 - ramp)

    @property
    def table_factor(self) -> float:
        """What cos and sin are multiplied by."""
        return _yarn_mscale(self.factor, self.mscale) / _yarn_mscale(
            self.factor, self.mscale_all_dim)

    @property
    def attention_factor(self) -> float:
        """What the softmax scale is multiplied by."""
        return _yarn_mscale(self.factor, self.mscale_all_dim) ** 2 \
            if self.mscale_all_dim else 1.0


def rope_tables(positions: jax.Array, theta: float, head_dim: int,
                yarn: Optional[Yarn] = None
                ) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [B, S, half] for these positions.  Computed once per
    forward and threaded through the layer scan as loop invariants rather
    than re-deriving the transcendentals per layer.  ``yarn``: the
    frequencies and the tables' factor of :class:`Yarn`."""
    half = head_dim // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn.inv_freq(theta, head_dim), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    if yarn is None or yarn.table_factor == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return (jnp.cos(angles) * yarn.table_factor,
            jnp.sin(angles) * yarn.table_factor)


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


def causal_conv(y, w, bias=None, tail=None):
    """Causal depthwise convolution along the sequence, then SiLU: ``y
    [B, S, *C]``, ``w [k, *C]``, ``out[t] = bias + sum_j w[j] y[t - (k-1)
    + j]`` (``w[k-1]`` meets the current token), float32 inside.  ``tail
    [B, k-1, *C]`` holds the ``k - 1`` inputs before ``y[0]`` (zeros where
    None: the sequence starts here); a decode tick hands it over with one
    token (:func:`conv_tail` gives the next one)."""
    kk, S = w.shape[0], y.shape[1]
    if tail is None:
        yp = jnp.pad(y.astype(jnp.float32),
                     ((0, 0), (kk - 1, 0)) + ((0, 0),) * (y.ndim - 2))
    else:
        yp = jnp.concatenate([tail.astype(jnp.float32),
                              y.astype(jnp.float32)], axis=1)
    out = sum(w[j].astype(jnp.float32) * yp[:, j:j + S] for j in range(kk))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out)


def conv_tail(y, k: int, tail=None, last=None):
    """The ``k - 1`` inputs that :func:`causal_conv` of what follows ``y``
    needs as its ``tail``: the last ``k - 1`` rows of ``tail`` then ``y``
    (zeros before the sequence where ``tail`` is None), ending at row
    ``last [B]`` of ``y`` where given (a right-padded prompt's last real
    token)."""
    B, S = y.shape[:2]
    prev = jnp.zeros((B, k - 1) + y.shape[2:], y.dtype) if tail is None \
        else tail.astype(y.dtype)
    yp = jnp.concatenate([prev, y], axis=1)
    end = jnp.full((B,), S, jnp.int32) if last is None else last + 1
    idx = end[:, None] + jnp.arange(k - 1)[None, :]   # rows of yp
    idx = idx.reshape(idx.shape + (1,) * (y.ndim - 2))
    return jnp.take_along_axis(yp, idx, axis=1)


def dense_mlp(x2, lp, name=lambda y, _: y):
    """SwiGLU MLP, in the frame's mlp form: ``(out, None)``.  ``name(y,
    what)`` marks the gate product (before the SiLU) and the up product;
    a training step passes ``checkpoint_name`` for its remat policy, and
    the serving programs, which a name would renumber, nothing."""
    g = jax.nn.silu(name(jnp.einsum("bsd,df->bsf", x2, lp["w_gate"]),
                         "mlp_gate"))
    u = name(jnp.einsum("bsd,df->bsf", x2, lp["w_up"]), "mlp_up")
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


@dataclasses.dataclass(frozen=True)
class HyperConnections:
    """The constants of manifold-constrained hyper-connections (mHC,
    arXiv:2512.24880): a residual of ``streams`` copies, mixed by a matrix
    that ``sinkhorn_iters`` rounds of Sinkhorn-Knopp bring near the doubly
    stochastic ones, ``eps`` in the stream's norm and in each divisor,
    the residual map's logits clamped to ``clamp``."""
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of dividing each row of ``m [..., n, n]`` by its
    sum, then each column by its sum (``+ eps`` in each divisor).  A
    loop, not the rounds written out: written out, XLA fuses the whole
    chain into the streams' mixing that consumes it, and the compile of
    one sub-layer at 1,024 tokens took over five minutes (a v5e target,
    ahead of time); the loop's body is two small fusions."""
    def round_(_, m):
        m = m / (m.sum(-1, keepdims=True) + eps)
        return m / (m.sum(-2, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, round_, m)


def _hc_logits(X, phi, eps: float):
    """``x~ phi`` in float32: ``x~`` a token's streams ``X [..., n, D]``
    flattened and divided by their root mean square (no gain)."""
    x = X.astype(jnp.float32).reshape(X.shape[:-2] + (-1,))
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return jnp.einsum("...k,km->...m", x, phi,
                      precision=jax.lax.Precision.HIGHEST) * r


def _hc_read(X, weights):
    """``sum_i weights_i X_i`` ``[..., D]`` in float32."""
    return sum(weights[..., i, None] * X[..., i, :].astype(jnp.float32)
               for i in range(X.shape[-2]))


def hc_maps(X, phi, bias, alpha, hc: HyperConnections):
    """A sub-layer's maps from the streams ``X [..., n, D]``: ``(pre
    [..., n], post [..., n], res [..., n, n])``, float32.  ``h = x~ phi``
    splits into ``n``, ``n`` and ``n * n`` columns; ``pre =
    sigmoid(alpha_0 h + b)``, ``post = 2 sigmoid(alpha_1 h + b)``, ``res =
    sinkhorn(exp(clamp(alpha_2 h + b)))``, row-major."""
    n = hc.streams
    h = _hc_logits(X, phi, hc.eps)
    pre = jax.nn.sigmoid(alpha[0] * h[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * h[..., n:2 * n] + bias[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * h[..., 2 * n:] + bias[2 * n:],
                           *hc.clamp))
    return pre, post, sinkhorn(res.reshape(res.shape[:-1] + (n, n)),
                               hc.sinkhorn_iters, hc.eps)


def _hc_branch(X, lp, name: str, norm: str, f, eps: float,
               hc: HyperConnections):
    """One sub-layer ``f`` in the stream frame: ``X <- res X + post^T
    f(norm(pre X))``; the maps and the mixing are the region ``hvd.hc``."""
    with region("hc"):
        pre, post, res = hc_maps(X, lp[name + "_phi"], lp[name + "_bias"],
                                 lp[name + "_alpha"], hc)
        u = _hc_read(X, pre).astype(X.dtype)
    y, extra = f(rmsnorm(u, lp[norm], eps), lp)
    with region("hc"):
        y = y.astype(jnp.float32)
        X = jnp.stack([_hc_read(X, res[..., i, :]) + post[..., i, None] * y
                       for i in range(hc.streams)], -2).astype(X.dtype)
    return X, extra


def hc_block(X, lp, mixer, mlp, eps: float, hc: HyperConnections):
    """The frame of a layer with hyper-connections: the residual is
    ``X [..., n, D]``, ``n`` streams, and each of the two sub-layers reads
    a mix of them and writes into every one (:func:`hc_maps`; the
    leaves ``hc_attn_*`` and ``hc_mlp_*``, ``phi [n D, n^2 + 2n]``, ``bias
    [n^2 + 2n]``, ``alpha [3]``).  ``mixer`` and ``mlp`` as
    :func:`block`'s, and so is what it returns; the halves are the
    regions ``hvd.block.mixer`` and ``hvd.block.mlp``, the maps and the
    mixing ``hvd.hc`` inside each."""
    with region("block.mixer"):
        X, kept = _hc_branch(X, lp, "hc_attn", "attn_norm", mixer, eps, hc)
    with region("block.mlp"):
        X, aux = _hc_branch(X, lp, "hc_mlp", "mlp_norm", mlp, eps, hc)
        return X, kept, aux


def hc_readout(X, phi, bias, alpha, hc: HyperConnections):
    """The one stream the head reads after the last layer, ``sum_i
    sigmoid(alpha x~ phi + b)_i X_i`` (``phi [n D, n]``), in ``X``'s type;
    the region ``hvd.hc``."""
    with region("hc"):
        w = jax.nn.sigmoid(alpha[0] * _hc_logits(X, phi, hc.eps) + bias)
        return _hc_read(X, w).astype(X.dtype)


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


# -- the GQA mixer's paged cache: what every model with gqa_mixer gives the
# serving steps (models.steps) ------------------------------------------------

def gqa_shard_rules(cfg, mesh: Optional[Mesh]) -> Optional[dict]:
    """Mesh-aware logical-rule overrides for ``cfg``'s heads.

    GQA configs where tp divides ``n_heads`` but not ``n_kv_heads`` (e.g.
    kv=2 on a tp=4 mesh) degrade the ``kv_heads`` rule to a dividing
    prefix or replication instead of failing init with an indivisible
    sharding — the flash path then keeps the kernel by expanding K/V at
    dispatch (see :func:`attention`)."""
    if mesh is None:
        return None
    return shd.fitted_rules(mesh, {
        "heads": cfg.n_heads,
        "kv_heads": cfg.n_kv_heads,
    })


def gqa_cache_rows(cfg) -> tuple:
    """Per-token shape of each pool of the paged cache: keys and values,
    grouped."""
    return ((cfg.n_kv_heads, cfg.head_dim),) * 2


def gqa_pool_dims(cfg) -> tuple:
    return POOL_DIMS


def gqa_paged_kernel_ok(cfg, mesh: Optional[Mesh], block_size: int,
                        interpret: bool = False) -> bool:
    """Whether the decode tick can take the paged kernel for this model,
    mesh and page size: tp must split q and kv heads alike (each chip
    runs the kernel on its own ``kv_heads`` shard), and the per-chip pool
    geometry must be one the kernel compiles for (any is, for the
    interpreter)."""
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        return False
    return interpret or FA.paged_supported(
        block_size, cfg.head_dim, cfg.n_kv_heads // tp, cfg.n_heads // tp,
        jnp.dtype(cfg.dtype).itemsize)


def _gqa_paged_kernel(q, kp, vp, layer, tables, lengths, scale, mesh,
                      rules, interpret):
    """The Pallas paged-decode kernel on q [B, H, Dh] against layer
    ``layer`` of the whole pools.  Under a mesh it is shard_mapped —
    batch rows over dp·fsdp, q heads and the pool's kv_heads over tp —
    for the same reason as the training kernel in :func:`attention`: a
    bare pallas_call has no GSPMD partitioning rule and would gather the
    pool onto every chip."""
    kernel = partial(FA.paged_attention, scale=scale, interpret=interpret)
    if mesh is None:
        return kernel(q, kp, vp, layer, tables, lengths)
    q_spec = shd.spec_for(("batch", "heads", None), rules)
    pool_spec = shd.spec_for(POOL_DIMS, rules)
    return shard_map(
        kernel, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(),
                  shd.spec_for(("batch", None), rules),
                  shd.spec_for(("batch",), rules)),
        out_specs=q_spec, check_vma=False)(
            q, kp, vp, layer, tables, lengths)


def gqa_paged_attend(cfg, mesh, tables, blk, off, mask, last=None,
                     interpret: bool = False):
    """The ``attend`` of the two paged steps (``models.steps``): each
    layer writes its fresh K/V rows into page ``blk`` at offset ``off``
    of its own pages first, then reads the table's logical window back:
    one query a row through the Pallas paged kernel where each stream's
    ``last`` position is given (a row whose table is all scratch has no
    stream, and the kernel skips it), else through the contiguous gather
    under ``mask`` (stale slots masked)."""
    scale = 1.0 / np.sqrt(cfg.head_dim)
    rules = gqa_shard_rules(cfg, mesh)

    def put(pool, li, new):
        # blk/off are [B] for one token a row, [B, S] for S.
        pool = pool.at[li, blk, off].set(
            new.reshape(blk.shape + new.shape[2:]))
        if mesh is None:
            return pool
        return shd.constrain(pool, POOL_DIMS, mesh, rules)

    # Block 0 is scratch and in no stream's table, and a row with no
    # stream is all block 0: it reaches the kernel as length 0, which the
    # kernel skips, where ``last + 1`` would read as a stream of one token.
    lengths = None if last is None else jnp.where(
        tables[:, 0] != 0, last + 1, 0)

    def attend(q, k1, v1, li, state):
        kp, vp = put(state[0], li, k1), put(state[1], li, v1)
        if lengths is not None:
            o = _gqa_paged_kernel(q[:, 0], kp, vp, li, tables, lengths,
                                  scale, mesh, rules, interpret)[:, None]
        else:
            o = cached_attend(q, gather_blocks(kp[li], tables),
                              gather_blocks(vp[li], tables), mask, scale)
        return o, ((kp, vp), None)

    return attend


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


def mla_expanded_attend(nope: int, mesh, scale: Optional[float] = None):
    """The ``attend`` of :func:`mla_mixer` in the expanded form, for a
    whole sequence (training, the tests' forward): every head's keys and
    values through the attention dispatch (the flash kernels), at the
    softmax ``scale`` (None: the key width's)."""
    def attend(q, c, k_pe, w_kvb):
        k, v = mla_expand(c, k_pe, w_kvb, nope)
        return attention(q, k, v, mesh, True, scale=scale), None
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
              sp_mode: str = "ring", scale: Optional[float] = None
              ) -> jax.Array:
    """Dispatch per :func:`attention_path`.  Under a mesh the sp paths and
    the flash kernel are shard_mapped so each chip works on its own
    batch/head shard (a bare pallas_call has no GSPMD partitioning rule
    and would be replicated).  ``scale``: the softmax scale where it is
    not the key width's ``D ** -0.5`` (the sp paths take no other)."""
    path = attention_path(q.shape, q.dtype.itemsize, mesh, sp_mode,
                          v.shape[-1])
    _log_attention_path(path, q.shape,
                        tuple(mesh.shape.items()) if mesh is not None
                        else None)
    if path in ("ring", "ulysses"):
        if scale is not None:
            raise NotImplementedError(
                f"{path} attention takes the key width's softmax scale only")
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
            q_, k_, v_, scale, causal, None, None, _FORCE_FLASH_INTERPRET)
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
    return FA.dense_attention(
        q, k, v, 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale,
        causal)


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


# -- the head and the loss ----------------------------------------------------

def final_logits(params, h, cfg, dims=None, mesh: Optional[Mesh] = None,
                 rules=None):
    """Final norm and lm_head on ``h [..., D]``: float32 logits, pinned to
    the logical ``dims`` under a mesh."""
    with region("head"):
        logits = jnp.einsum(
            "...d,dv->...v", rmsnorm(h, params["final_norm"], cfg.rms_eps),
            params["lm_head"])
        if mesh is not None:
            logits = shd.constrain(logits, dims, mesh, rules)
        return logits.astype(jnp.float32)


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
