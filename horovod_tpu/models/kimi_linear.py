"""Kimi-Linear: KDA linear-attention layers, NoPE latent attention and a
sigmoid-routed MoE (moonshotai/Kimi-Linear-48B-A3B, arXiv:2510.26692).

Pre-norm residual stack, RMSNorm, untied head, no positional encoding
anywhere (``mla_use_nope``: the KDA layers carry position in their
recurrence and their short convolution).  Three kinds of layer:

- **KDA mixer** (:mod:`horovod_tpu.ops.kda`): ``q, k, v =
  silu(causal_depthwise_conv(x W))``, q and k L2-normalised per head, a
  per-channel log decay ``g = -exp(A_log) * softplus((x W_fa) W_fb +
  dt_bias)``, ``beta = sigmoid(x W_b)``, the gated delta rule, then
  ``rmsnorm(o) * o_norm * sigmoid((x W_ga) W_gb)`` through ``W_o``.
- **MLA mixer** (:func:`horovod_tpu.models.layers.mla_mixer`, the one
  GLM-4.7-Flash shares): full-rank queries of nope + rope width, keys
  and values expanded from a normalised latent of ``kv_lora_rank`` plus
  one shared ``k_pe`` per token, no rotation; causal softmax attention
  with keys wider than values, through the flash kernels the Llama path
  uses.
- **MLP**: SwiGLU, dense in the first ``first_k_dense`` layers, then the
  expert layer of :func:`horovod_tpu.parallel.moe.moe_layer_held`:
  sigmoid scores, a selection bias, top-k, renormalised and scaled
  weights, a shared expert, and the share ``[held_first, held_first +
  experts_held)`` of the routed experts that this chip holds.

A layer kind is a ``(mixer, mlp)`` pair (:func:`layer_pair`) in the one
pre-norm frame of :func:`horovod_tpu.models.layers.block`.  Layers of
different kinds cannot be one ``lax.scan`` over one stacked tree.  The
stack is cut into **runs** of consecutive layers of one kind, in the
published order (:func:`layer_runs`); ``params["runs"]`` holds one
stacked tree per run and each run is scanned, remat per layer.

Trains through :func:`horovod_tpu.models.steps.make_train_step` (which
finds this module by the configuration's class); the optimizer goes
through :func:`optimizer`, because the router's selection bias is a
buffer: the published model moves it by a balancing rule outside the
gradient, so it takes no gradient and no update here.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.trace import region
from ..ops.kda import CHUNK, chunk_kda
from ..parallel.moe import moe_layer_held
from .layers import (
    block,
    causal_conv,
    causal_lm_loss,
    dense_mlp,
    embed_lookup,
    mla_expanded_attend,
    mla_mixer,
    remat,
    rmsnorm,
)

# steps.make_train_step: the third output of the step is (loss, stats).
LOSS_HAS_AUX = True


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    # Rows of the vocabulary held here: ids, logits and loss are over them.
    vocab_rows: int = 163840
    d_model: int = 2304
    n_layers: int = 27
    # 1-based layer numbers, as the published linear_attn_config has them.
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                         21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    first_k_dense: int = 1
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    gate_rank: int = 128          # low-rank width of the decay and output gates
    n_heads: int = 32             # MLA
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 9216
    moe_d_ff: int = 1024
    n_experts: int = 256          # the router's outputs
    experts_per_token: int = 8
    routed_scale: float = 2.446
    renormalize: bool = True
    # The share of the routed experts held here.
    experts_held: int = 256
    held_first: int = 0
    rms_eps: float = 1e-5
    l2_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: Any = True
    blockwise_ce: bool = False
    kda_chunk: int = CHUNK
    moe_tile: int = 512

    @property
    def held_range(self) -> tuple:
        return self.held_first, self.held_first + self.experts_held

    @staticmethod
    def from_published(c: dict, **kw) -> "KimiLinearConfig":
        """From the keys of the published ``config.json`` (as cut by a
        configuration file: ``num_experts`` there counts the experts
        held, ``vocab_size`` the rows held)."""
        la = c["linear_attn_config"]
        base = dict(
            vocab_rows=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            kda_layers=tuple(la["kda_layers"]),
            full_attn_layers=tuple(la["full_attn_layers"]),
            first_k_dense=c["first_k_dense_replace"],
            kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
            conv_kernel=la["short_conv_kernel_size"],
            gate_rank=la["head_dim"], n_heads=c["num_attention_heads"],
            kv_lora_rank=c["kv_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
            qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
            d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
            experts_per_token=c["num_experts_per_token"],
            routed_scale=c["routed_scaling_factor"],
            renormalize=c["moe_renormalize"], rms_eps=c["rms_norm_eps"])
        base.update(kw)
        return KimiLinearConfig(**base)

    @staticmethod
    def tiny(**kw) -> "KimiLinearConfig":
        """Test-scale config in the published pattern (fast CPU compile)."""
        base = dict(vocab_rows=128, d_model=64, n_layers=5,
                    kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
                    kda_heads=2, kda_head_dim=16, gate_rank=16, n_heads=2,
                    kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, d_ff=128, moe_d_ff=32, n_experts=16,
                    experts_per_token=2, experts_held=16,
                    dtype=jnp.float32, remat=False, kda_chunk=16, moe_tile=8)
        base.update(kw)
        return KimiLinearConfig(**base)


def layer_kind(cfg: KimiLinearConfig, layer: int) -> str:
    """``kda_dense``, ``kda_moe``, ``mla_dense`` or ``mla_moe`` for the
    1-based ``layer``."""
    if (layer in cfg.kda_layers) == (layer in cfg.full_attn_layers):
        raise ValueError(f"layer {layer} must be in exactly one of "
                         "kda_layers and full_attn_layers")
    mixer = "kda" if layer in cfg.kda_layers else "mla"
    return mixer + ("_dense" if layer <= cfg.first_k_dense else "_moe")


def layer_runs(cfg: KimiLinearConfig) -> list:
    """``[(kind, first layer, count)]``: consecutive layers of one kind,
    in the published order."""
    runs: list = []
    for layer in range(1, cfg.n_layers + 1):
        kind = layer_kind(cfg, layer)
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, layer, 1])
    return [tuple(r) for r in runs]


# -- parameters ---------------------------------------------------------------

def leaf_shapes(cfg: KimiLinearConfig, kind: str) -> dict:
    """One layer's leaves: ``name -> (shape, fan_in)``; fan_in None marks
    a float32 leaf with an initialisation of its own (gains, ``A_log``,
    ``dt_bias``, the router and its bias)."""
    D, H, K, R = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, cfg.gate_rank
    mixer, mlp = kind.split("_")
    out = {"attn_norm": ((D,), None), "mlp_norm": ((D,), None)}
    if mixer == "kda":
        out.update({
            "wq": ((D, H, K), D), "wk": ((D, H, K), D), "wv": ((D, H, K), D),
            "conv_q": ((cfg.conv_kernel, H, K), cfg.conv_kernel),
            "conv_k": ((cfg.conv_kernel, H, K), cfg.conv_kernel),
            "conv_v": ((cfg.conv_kernel, H, K), cfg.conv_kernel),
            "w_fa": ((D, R), D), "w_fb": ((R, H, K), R),
            "dt_bias": ((H, K), None), "A_log": ((H,), None),
            "w_ga": ((D, R), D), "w_gb": ((R, H, K), R),
            "w_beta": ((D, H), D), "o_norm": ((K,), None),
            "wo": ((H, K, D), H * K)})
    else:
        Hm, C = cfg.n_heads, cfg.kv_lora_rank
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        out.update({
            "wq": ((D, Hm, qk), D), "w_kva": ((D, C + cfg.qk_rope_dim), D),
            "kv_norm": ((C,), None),
            "w_kvb": ((C, Hm, cfg.qk_nope_dim + cfg.v_head_dim), C),
            "wo": ((Hm, cfg.v_head_dim, D), Hm * cfg.v_head_dim)})
    if mlp == "dense":
        F = cfg.d_ff
        out.update({"w_gate": ((D, F), D), "w_up": ((D, F), D),
                    "w_down": ((F, D), F)})
    else:
        E, Eh, F = cfg.n_experts, cfg.experts_held, cfg.moe_d_ff
        out.update({
            "router": ((D, E), None), "router_bias": ((E,), None),
            "e_gate": ((Eh, D, F), D), "e_up": ((Eh, D, F), D),
            "e_down": ((Eh, F, D), F),
            "s_gate": ((D, F), D), "s_up": ((D, F), D), "s_down": ((F, D), F)})
    return out


def _special_leaf(name: str, shape: tuple, key):
    if name == "A_log":          # log of uniform(1, 16), as the family's code
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":        # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "router":
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
    if name == "router_bias":
        return 0.01 * jax.random.normal(key, shape, jnp.float32)
    return jnp.ones(shape, jnp.float32)               # norm gains


def init_params(cfg: KimiLinearConfig, key: jax.Array) -> dict:
    """The program's own seeded leaves (placed by the caller:
    :func:`param_shardings`)."""
    D, V = cfg.d_model, cfg.vocab_rows
    rnd = lambda k, shape, fan: (jax.random.normal(
        k, shape, jnp.float32) / np.sqrt(fan)).astype(cfg.dtype)
    runs = []
    for r, (kind, _, n) in enumerate(layer_runs(cfg)):
        shapes = leaf_shapes(cfg, kind)
        ks = jax.random.split(jax.random.fold_in(key, r), len(shapes))
        runs.append({
            name: (rnd(k, (n,) + shape, fan) if fan is not None else
                   jax.vmap(lambda kk: _special_leaf(name, shape, kk)
                            )(jax.random.split(k, n)))
            for k, (name, (shape, fan)) in zip(ks, sorted(shapes.items()))})
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed": rnd(ke, (V, D), D), "runs": runs,
            "final_norm": jnp.ones((D,), jnp.float32),
            "lm_head": rnd(kh, (D, V), D)}


def param_shardings(cfg: KimiLinearConfig, mesh: Mesh) -> dict:
    """Every leaf replicated: a chip holds its whole share of the model,
    and further chips of the mesh are data-parallel replicas of it (the
    batch is split over dp and fsdp by ``make_train_step``)."""
    repl = NamedSharding(mesh, P())
    aval = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda _: repl, aval)


def trainable(params: dict) -> dict:
    """True for every leaf the optimizer moves: all but the routers'
    selection biases."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) != "router_bias",
        params)


def optimizer(tx):
    """``tx`` over the trainable leaves; the selection bias passes through
    with its (stopped, zero) gradient as its update."""
    import optax
    return optax.masked(tx, trainable)


# -- the layers ---------------------------------------------------------------

def _l2norm(x, eps):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _kda_mixer(x, lp, cfg: KimiLinearConfig):
    dt = x.dtype
    proj = lambda w, conv: causal_conv(
        jnp.einsum("bsd,dhk->bshk", x, lp[w]), lp[conv])
    q = _l2norm(proj("wq", "conv_q"), cfg.l2_eps) * cfg.kda_head_dim ** -0.5
    k = _l2norm(proj("wk", "conv_k"), cfg.l2_eps)
    v = proj("wv", "conv_v")
    low = lambda a, b: jnp.einsum(
        "bsr,rhk->bshk", jnp.einsum("bsd,dr->bsr", x, lp[a]), lp[b]
    ).astype(jnp.float32)
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        low("w_fa", "w_fb") + lp["dt_bias"])
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, lp["w_beta"]
                                     ).astype(jnp.float32))
    o = chunk_kda(q.astype(dt), k.astype(dt), v.astype(dt), g, beta,
                  cfg.kda_chunk)
    o = rmsnorm(o, lp["o_norm"], cfg.rms_eps) * \
        jax.nn.sigmoid(low("w_ga", "w_gb")).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), None


def _moe_mlp(x2, lp, cfg: KimiLinearConfig):
    B, S, D = x2.shape
    out, stats = moe_layer_held(
        x2.reshape(B * S, D), lp["router"],
        lax.stop_gradient(lp["router_bias"]),
        {"gate": lp["e_gate"], "up": lp["e_up"], "down": lp["e_down"]},
        cfg.held_range,
        {"w_gate": lp["s_gate"], "w_up": lp["s_up"], "w_down": lp["s_down"]},
        k=cfg.experts_per_token, renormalize=cfg.renormalize,
        scale=cfg.routed_scale, tile=cfg.moe_tile)
    return out.reshape(B, S, D), stats


def layer_pair(kind: str, cfg: KimiLinearConfig, mesh) -> tuple:
    """The ``(mixer, mlp)`` of a layer kind, each ``(x, lp) -> (y,
    extra)`` as :func:`horovod_tpu.models.layers.block` takes them."""
    mixer, mlp = kind.split("_")
    return (partial(_kda_mixer, cfg=cfg) if mixer == "kda"
            else partial(mla_mixer, nope=cfg.qk_nope_dim, eps=cfg.rms_eps,
                         tables=None,
                         attend=mla_expanded_attend(cfg.qk_nope_dim, mesh)),
            dense_mlp if mlp == "dense" else partial(_moe_mlp, cfg=cfg))


def forward(params: dict, tokens: jax.Array, cfg: KimiLinearConfig, *,
            mesh: Optional[Mesh] = None, return_hidden: bool = False):
    """Logits ``[B, S, vocab_rows]`` (float32) and the routing counts of
    the expert layers, in layer order: ``{"pairs_held": [n_moe],
    "expert_counts": [n_moe, experts_held]}``."""
    h = embed_lookup(params["embed"], tokens, cfg.dtype)
    stats = []
    for (kind, _, _), stack in zip(layer_runs(cfg), params["runs"]):
        mixer, mlp = layer_pair(kind, cfg, mesh)

        def body(h, lp):         # traced here, with this run's pair
            h, _, st = block(h, lp, mixer, mlp, cfg.rms_eps)
            return h, st

        # Plain remat: ``llama.remat_plan``'s estimate is a Llama step's,
        # blind to the KDA states, and the rule fed this cell's candidates
        # and the 1.7 GB its step leaves free keeps nothing anyway.
        h, st = lax.scan(remat(body, cfg.remat), h, stack)
        if st is not None:
            stats.append(st)
    stats = jax.tree.map(lambda *a: jnp.concatenate(a), *stats) \
        if stats else {}
    with region("head"):
        h = rmsnorm(h, params["final_norm"], cfg.rms_eps)
        if return_hidden:
            return h, stats
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
        return logits.astype(jnp.float32), stats


def loss_fn(params: dict, batch: dict, cfg: KimiLinearConfig, *,
            mesh: Optional[Mesh] = None):
    """Causal LM loss over the rows held: ``batch = {"tokens": [B, S+1]}``.
    Returns ``(loss, routing counts)``; no auxiliary loss."""
    return causal_lm_loss(
        lambda inputs, hidden: forward(params, inputs, cfg, mesh=mesh,
                                       return_hidden=hidden),
        params["lm_head"], batch["tokens"], cfg.blockwise_ce)


def record_routing(cfg: KimiLinearConfig, stats: dict) -> None:
    """Host side, after a step: count the step's routing (``stats`` as
    :func:`forward` returns them, fetched) into the per-layer metrics
    ``hvd_moe_held_pairs_total``, ``hvd_moe_expert_load_max_over_mean``
    and ``hvd_moe_combine_steps_total``."""
    from ..parallel.moe import record_held_pairs
    moe_layers = [l for l in range(1, cfg.n_layers + 1)
                  if layer_kind(cfg, l).endswith("_moe")]
    for layer, counts in zip(moe_layers, np.asarray(stats["expert_counts"])):
        record_held_pairs(counts, layer=str(layer), scored=cfg.n_experts)
