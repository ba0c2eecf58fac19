"""Xing4.0-29B-A4B (XingChen-AGI, ``model_type: xing4_0``): GLM-4.7-Flash's
latent attention and sigmoid-routed experts around a residual of four
streams mixed by manifold-constrained hyper-connections (mHC,
arXiv:2512.24880), and YaRN on the rope columns.

What is GLM-4.7-Flash's is imported from
:mod:`horovod_tpu.models.glm_moe_lite` and not written again: the
leaves of attention and of the expert layers, the layer runs (dense
layers first, ``first_k_dense_replace`` of them), the expert layer
(``_moe_mlp``, ``moe_tile``), the latent mixer, both ``attend`` forms,
the pool hooks, the embedding's lookup and the refusals (no training
objective, no mesh).  What differs:

- **the frame** (:func:`horovod_tpu.models.layers.hc_block`): the
  residual is ``X [B, S, n, D]``, ``n = hc_mult`` streams, the token's
  embedding in each at the start.  Each sub-layer ``f`` (attention, then
  the MLP) has its own maps from ``x~ = vec(X) / rms(vec(X))``:
  ``pre = sigmoid(a x~ phi + b)``, ``post = 2 sigmoid(...)`` and
  ``res``, ``hc_sinkhorn_iters`` rounds of Sinkhorn-Knopp on
  ``exp(clamp(...))``; ``X <- res X + post^T f(RMSNorm(pre X))``.  After
  the last layer the head reads ``sum_i sigmoid(a x~ phi + b)_i X_i``
  (:func:`serve_readout`).  Maps in float32, the streams carried in the
  served type;
- **YaRN** on the rope columns (:class:`horovod_tpu.models.layers.Yarn`)
  and its ``mscale**2`` in the softmax scale of both attention forms.

Served by :class:`horovod_tpu.serving.ServingEngine` through the steps of
:mod:`horovod_tpu.models.steps`, which find this module by the
configuration's class and ask it for its frame and its read-out.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from ..obs.trace import region
from .glm_moe_lite import (  # noqa: F401  (the serving hooks, re-exported)
    PAGED_KERNEL,
    GlmMoeLiteConfig,
    _mixer,
    _mlp,
    cache_rows,
    check_servable,
    check_trainable,
    layer_runs,
    leaf_shapes as _glm_leaf_shapes,
    moe_experts_scored,
    moe_layer_names,
    paged_attend,
    paged_kernel_ok,
    pool_dims,
    prefill_attend,
    prefill_path,
    serve_embed as _glm_embed,
    serve_runs,
    serve_stats,
)
from .layers import (
    HyperConnections,
    Yarn,
    hc_block,
    hc_readout,
    mla_expanded_attend,
    rmsnorm,
    rope_tables,
)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Xing4Config(GlmMoeLiteConfig):
    vocab_size: int = 131072
    d_model: int = 3584
    n_layers: int = 40
    first_k_dense: int = 2
    n_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 9216
    moe_d_ff: int = 1024
    n_experts: int = 64
    experts_per_token: int = 4
    routed_scale: float = 2.0
    rope_theta: float = 1e4
    rms_eps: float = 1e-6
    yarn: Optional[Yarn] = Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    hc: HyperConnections = HyperConnections()

    @staticmethod
    def from_published(c: dict, **kw) -> "Xing4Config":
        """From the keys of the published ``config.json``."""
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("n_shared_experts", 1), ("moe_layer_freq", 1),
                          ("attention_bias", False), ("hidden_act", "silu"),
                          ("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"),
                          ("tie_word_embeddings", False)):
            if c[key] != want:
                raise NotImplementedError(
                    f"xing4: {key}={c[key]!r} is not written here "
                    f"(only {want!r})")
        rs = c["rope_scaling"]
        if rs is None or rs["type"] != "yarn":
            raise NotImplementedError(
                f"xing4: rope_scaling={rs!r} is not written here (only yarn)")
        base = dict(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            first_k_dense=c["first_k_dense_replace"],
            n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_dim=c["qk_nope_head_dim"],
            qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
            d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
            n_experts=c["n_routed_experts"],
            experts_per_token=c["num_experts_per_tok"],
            routed_scale=float(c["routed_scaling_factor"]),
            renormalize=c["norm_topk_prob"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            yarn=Yarn(float(rs["factor"]),
                      int(rs["original_max_position_embeddings"]),
                      float(rs["beta_fast"]), float(rs["beta_slow"]),
                      float(rs["mscale"]), float(rs["mscale_all_dim"])),
            hc=HyperConnections(
                c["hc_mult"], c["hc_sinkhorn_iters"], float(c["hc_eps"]),
                (float(c["mhc_h_res_clamp_min"]),
                 float(c["mhc_h_res_clamp_max"]))))
        base.update(kw)
        return Xing4Config(**base)

    @staticmethod
    def tiny(**kw) -> "Xing4Config":
        """Test-scale config in the published pattern (fast CPU compile):
        four streams, two dense layers and two expert layers, 8 experts, 2
        a token, a YaRN ramp over the middle of the rope frequencies."""
        base = dict(vocab_size=128, d_model=64, n_layers=4, n_heads=2,
                    q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=16,
                    qk_rope_dim=8, v_head_dim=16, d_ff=128, moe_d_ff=32,
                    n_experts=8, experts_per_token=2, dtype=jnp.float32,
                    yarn=Yarn(4.0, 256, 32.0, 1.0, 1.0, 1.0))
        base.update(kw)
        return Xing4Config(**base)


# -- parameters ---------------------------------------------------------------

def hc_leaf_shapes(cfg: Xing4Config) -> dict:
    """A layer's map leaves, float32: ``name -> shape``."""
    n, D = cfg.hc.streams, cfg.d_model
    m = n * n + 2 * n
    return {f"hc_{f}_{leaf}": shape for f in ("attn", "mlp")
            for leaf, shape in (("phi", (n * D, m)), ("bias", (m,)),
                                ("alpha", (3,)))}


def head_leaf_shapes(cfg: Xing4Config) -> dict:
    """The read-out's leaves, float32."""
    n = cfg.hc.streams
    return {"hc_head_phi": (n * cfg.d_model, n), "hc_head_bias": (n,),
            "hc_head_alpha": (1,)}


def leaf_shapes(cfg: Xing4Config, kind: str) -> dict:
    """One layer's leaves: ``name -> (shape, fan_in)``; fan_in None marks
    a float32 leaf (gains, the router and its bias, the maps)."""
    return {**_glm_leaf_shapes(cfg, kind),
            **{k: (s, None) for k, s in hc_leaf_shapes(cfg).items()}}


def _f32_leaf(name: str, shape: tuple, key):
    """The program's own draw of a float32 leaf (the benchmark's weights
    are its own): maps as a 1/sqrt(fan_in) normal, ``alpha`` 1, the
    router's bias 0, gains 1."""
    if name == "router" or name.endswith("_phi"):
        return jax.random.normal(key, shape, F32) / np.sqrt(shape[0])
    if name.endswith("_bias"):
        return jnp.zeros(shape, F32)
    return jnp.ones(shape, F32)


def init_params(cfg: Xing4Config, key: jax.Array) -> dict:
    """The program's own seeded leaves, in the layout it serves."""
    D, V = cfg.d_model, cfg.vocab_size
    rnd = lambda k, shape, fan: (jax.random.normal(
        k, shape, F32) / np.sqrt(fan)).astype(cfg.dtype)
    runs = []
    for r, (kind, n) in enumerate(layer_runs(cfg)):
        shapes = leaf_shapes(cfg, kind)
        ks = jax.random.split(jax.random.fold_in(key, r), len(shapes))
        runs.append({
            name: (rnd(k, (n,) + shape, fan) if fan is not None else
                   jax.vmap(lambda kk: _f32_leaf(name, shape, kk)
                            )(jax.random.split(k, n)))
            for k, (name, (shape, fan)) in zip(ks, sorted(shapes.items()))})
    ke, kh, kr = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    head = {name: _f32_leaf(name, shape, k) for k, (name, shape) in zip(
        jax.random.split(kr, 3), sorted(head_leaf_shapes(cfg).items()))}
    return {"embed": rnd(ke, (V, D), D), "runs": runs,
            "final_norm": jnp.ones((D,), F32),
            "lm_head": rnd(kh, (D, V), D), **head}


# -- the stream frame ---------------------------------------------------------

def serve_frame(cfg: Xing4Config):
    """The frame of every layer: :func:`~horovod_tpu.models.layers.hc_block`
    over ``cfg.hc``."""
    return partial(hc_block, hc=cfg.hc)


def serve_embed(embed, tokens, cfg: Xing4Config):
    """The token's embedding in each of the streams: ``[B, S, n, D]``."""
    e = _glm_embed(embed, tokens, cfg)
    with region("embed"):
        return jnp.broadcast_to(e[..., None, :], e.shape[:-1] + (
            cfg.hc.streams, e.shape[-1]))


def serve_readout(params, X, cfg: Xing4Config):
    """The one mix of the streams the final norm reads, inside
    ``hvd.head``."""
    with region("head"):
        return hc_readout(X, params["hc_head_phi"], params["hc_head_bias"],
                          params["hc_head_alpha"], cfg.hc)


def forward(params: dict, tokens: jax.Array, cfg: Xing4Config, *,
            mesh: Optional[Mesh] = None, return_hidden: bool = False,
            picks: bool = False):
    """As :func:`horovod_tpu.models.glm_moe_lite.forward`, in the stream
    frame: logits ``[B, S, V]`` float32 (with ``return_hidden`` the final
    normed hidden states) and the expert layers' routing."""
    B, S = tokens.shape
    X = serve_embed(params["embed"], tokens, cfg)
    tables = rope_tables(jnp.broadcast_to(jnp.arange(S), (B, S)),
                         cfg.rope_theta, cfg.qk_rope_dim, cfg.yarn)
    mixer = _mixer(cfg, tables, mla_expanded_attend(
        cfg.qk_nope_dim, mesh, cfg.softmax_scale))
    frame = serve_frame(cfg)
    stats = []
    for (kind, _), stack in zip(layer_runs(cfg), params["runs"]):
        mlp = _mlp(kind, cfg, picks=picks)

        def body(X, lp):         # traced here, with this run's pair
            X, _, st = frame(X, lp, mixer, mlp, cfg.rms_eps)
            return X, st

        X, st = lax.scan(body, X, stack)
        if st is not None:
            stats.append(st)
    stats = jax.tree.map(lambda *a: jnp.concatenate(a), *stats) \
        if stats else {}
    h = serve_readout(params, X, cfg)
    with region("head"):
        h = rmsnorm(h, params["final_norm"], cfg.rms_eps)
        if return_hidden:
            return h, stats
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
        return logits.astype(F32), stats
