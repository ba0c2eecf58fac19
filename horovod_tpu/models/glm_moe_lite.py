"""GLM-4.7-Flash (zai-org, ``model_type: glm4_moe_lite``): latent attention
with a compressed query and a rotated shared key, a dense first layer,
then sigmoid-routed experts with a shared expert.

Pre-norm residual stack (:func:`horovod_tpu.models.layers.block`),
RMSNorm, untied head.  Two kinds of layer, in runs (``dense`` for the
first ``first_k_dense`` layers, ``moe`` after them), each run stacked
and scanned:

- **mixer**, both kinds: :func:`horovod_tpu.models.layers.mla_mixer`,
  the one Kimi-Linear's full-attention layers use, here with the query
  through ``q_lora_rank`` and its norm and with rotation of the last
  ``qk_rope_dim`` columns (half-split, ``rope_theta``, no scaling).
  A token leaves ONE cache entry a layer, for all heads and for keys and
  values alike: the normed latent and the rotated shared key,
  ``kv_lora_rank + qk_rope_dim`` = 576 values.  A prompt is attended in
  the expanded form (every head's keys and values through the flash
  kernel); a decode tick in the absorbed form, the query taken into the
  latent's space, against the paged pool of such entries
  (:func:`horovod_tpu.ops.flash_attention.mla_paged_attention`).
- **mlp**: SwiGLU of ``d_ff``, or
  :func:`horovod_tpu.parallel.moe.moe_layer_held` with every expert held:
  sigmoid scores in float32, a selection bias, the ``experts_per_token``
  largest, renormalised, scaled, and a shared expert; dropless, the
  grouped products' tile following the step's rows (:func:`moe_tile`),
  their results back to the rows through the list (every scored expert
  is held: :func:`horovod_tpu.parallel.moe.combine_form`).

Served by :class:`horovod_tpu.serving.ServingEngine` through the three
steps of :mod:`horovod_tpu.models.llama`, which find this module by the
configuration's class; the functions under "serving" are what they ask a
model for.  Not trained here: the published model's objective has a
second term, a multi-token prediction module
(``num_nextn_predict_layers``) that reads the last hidden state, which
is neither loaded nor written (:func:`check_trainable` raises, so
``make_train_step(model=glm_moe_lite)`` does).
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
from ..ops import flash_attention as FA
from ..parallel.moe import moe_layer_held
from .layers import (
    attention,
    attention_path,
    block,
    cached_attend,
    dense_mlp,
    embed_lookup,
    gather_blocks,
    mla_absorb,
    mla_expand,
    mla_expanded_attend,
    mla_mixer,
    mla_row,
    rmsnorm,
    rope_tables,
)

#: what :attr:`ServingEngine.attention_path` calls this model's kernel
PAGED_KERNEL = "pallas-mla"


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
    vocab_size: int = 154880
    d_model: int = 2048
    n_layers: int = 47
    first_k_dense: int = 1
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 192
    qk_rope_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10240
    moe_d_ff: int = 1536
    n_experts: int = 64
    experts_per_token: int = 4
    routed_scale: float = 1.8
    renormalize: bool = True
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    #: a plain stack: one pass, a cache layer a layer
    loops = 1

    @property
    def cache_layers(self) -> int:
        return self.n_layers

    @property
    def cache_values(self) -> int:
        """Values a token leaves in one cache layer, as published."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def cache_row(self) -> int:
        """Width of a token's row in the pool: the published values
        padded to whole 128-lane tiles (576 -> 640), which is what an
        array of 576 columns takes in the chip's memory anyway and what
        the decode kernel can slice a page out of."""
        return -(-self.cache_values // 128) * 128

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @staticmethod
    def from_published(c: dict, **kw) -> "GlmMoeLiteConfig":
        """From the keys of the published ``config.json``."""
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("n_shared_experts", 1), ("rope_scaling", None),
                          ("partial_rotary_factor", 1),
                          ("attention_bias", False), ("hidden_act", "silu"),
                          ("topk_method", "noaux_tc")):
            if c[key] != want:
                raise NotImplementedError(
                    f"glm_moe_lite: {key}={c[key]!r} is not written here "
                    f"(only {want!r})")
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
            routed_scale=c["routed_scaling_factor"],
            renormalize=c["norm_topk_prob"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"])
        base.update(kw)
        return GlmMoeLiteConfig(**base)

    @staticmethod
    def tiny(**kw) -> "GlmMoeLiteConfig":
        """Test-scale config in the published pattern (fast CPU compile):
        one dense layer and two expert layers, 8 experts, 2 a token."""
        base = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=2,
                    q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=16,
                    qk_rope_dim=8, v_head_dim=16, d_ff=128, moe_d_ff=32,
                    n_experts=8, experts_per_token=2, dtype=jnp.float32)
        base.update(kw)
        return GlmMoeLiteConfig(**base)


def layer_runs(cfg: GlmMoeLiteConfig) -> list:
    """``[(kind, count)]`` in layer order: the dense layers, then the
    expert layers."""
    dense = min(cfg.first_k_dense, cfg.n_layers)
    runs = [("dense", dense), ("moe", cfg.n_layers - dense)]
    return [r for r in runs if r[1]]


# -- parameters ---------------------------------------------------------------

def leaf_shapes(cfg: GlmMoeLiteConfig, kind: str) -> dict:
    """One layer's leaves: ``name -> (shape, fan_in)``; fan_in None marks
    a float32 leaf (gains, the router and its selection bias)."""
    D, H, C = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    out = {
        "attn_norm": ((D,), None), "mlp_norm": ((D,), None),
        "w_qa": ((D, cfg.q_lora_rank), D),
        "q_norm": ((cfg.q_lora_rank,), None),
        "w_qb": ((cfg.q_lora_rank, H, cfg.qk_dim), cfg.q_lora_rank),
        "w_kva": ((D, C + cfg.qk_rope_dim), D), "kv_norm": ((C,), None),
        "w_kvb": ((C, H, cfg.qk_nope_dim + cfg.v_head_dim), C),
        "wo": ((H, cfg.v_head_dim, D), H * cfg.v_head_dim)}
    if kind == "dense":
        F = cfg.d_ff
        out.update({"w_gate": ((D, F), D), "w_up": ((D, F), D),
                    "w_down": ((F, D), F)})
    else:
        E, F = cfg.n_experts, cfg.moe_d_ff
        out.update({
            "router": ((D, E), None), "router_bias": ((E,), None),
            "e_gate": ((E, D, F), D), "e_up": ((E, D, F), D),
            "e_down": ((E, F, D), F),
            "s_gate": ((D, F), D), "s_up": ((D, F), D), "s_down": ((F, D), F)})
    return out


def _special_leaf(name: str, shape: tuple, key):
    if name == "router":
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    return jnp.ones(shape, jnp.float32)               # norm gains


def init_params(cfg: GlmMoeLiteConfig, key: jax.Array,
                mesh: Optional[Mesh] = None) -> dict:
    def build(key):
        D, V = cfg.d_model, cfg.vocab_size
        rnd = lambda k, shape, fan: (jax.random.normal(
            k, shape, jnp.float32) / np.sqrt(fan)).astype(cfg.dtype)
        runs = []
        for r, (kind, n) in enumerate(layer_runs(cfg)):
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

    if mesh is None:
        return build(key)
    return jax.jit(build, out_shardings=param_shardings(cfg, mesh))(key)


def param_shardings(cfg: GlmMoeLiteConfig, mesh: Mesh) -> dict:
    """Every leaf replicated: a chip holds its whole stage."""
    repl = NamedSharding(mesh, P())
    aval = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda _: repl, aval)


# -- the layers ---------------------------------------------------------------

def moe_tile(rows: int) -> int:
    """Rows of a tile of the grouped expert products, from the step's
    rows: a step of up to 256 rows (a decode tick's 64, a verify step's
    few hundred) is one tile, so an expert, which no token takes twice,
    is at most one tile and its three matrices are read once; a prompt's
    thousands go 256 at a time, where a tile's products take about as
    long as reading its expert's matrices (the v5e's 240 FLOPs a byte)
    and the padding of an expert's last tile stays a third of the work
    at the mean load, where 512 made it two thirds."""
    return min(256, -(-rows // 16) * 16)


_EXPERT_LEAVES = {"gate": "e_gate", "up": "e_up", "down": "e_down"}


def _moe_mlp(x2, lp, cfg: GlmMoeLiteConfig, picks: bool = False,
             stack=None):
    """The expert layer as the frame's mlp; its extra is the layer's
    pairs by expert ``[E]`` (with ``picks`` also each token's experts and
    every selection score, for the comparison with the reference).  The
    experts are the layer's own leaves, or with ``stack`` the whole run's
    (``[layers * E, ...]``) from row ``lp["expert_row"]`` on."""
    B, S, D = x2.shape
    out, stats = moe_layer_held(
        x2.reshape(B * S, D), lp["router"], lp["router_bias"],
        stack or {k: lp[leaf] for k, leaf in _EXPERT_LEAVES.items()},
        (0, cfg.n_experts),
        {"w_gate": lp["s_gate"], "w_up": lp["s_up"], "w_down": lp["s_down"]},
        k=cfg.experts_per_token, renormalize=cfg.renormalize,
        scale=cfg.routed_scale, tile=moe_tile(B * S), picks=picks,
        first_row=lp.get("expert_row"))
    keep = ("expert_counts", "experts", "scores") if picks \
        else ("expert_counts",)
    return out.reshape(B, S, D), {k: stats[k] for k in keep}


def _mixer(cfg: GlmMoeLiteConfig, tables, attend):
    return partial(mla_mixer, nope=cfg.qk_nope_dim, eps=cfg.rms_eps,
                   tables=tables, attend=attend)


def _mlp(kind: str, cfg: GlmMoeLiteConfig, **kw):
    return dense_mlp if kind == "dense" else partial(_moe_mlp, cfg=cfg, **kw)


def forward(params: dict, tokens: jax.Array, cfg: GlmMoeLiteConfig, *,
            mesh: Optional[Mesh] = None, return_hidden: bool = False,
            picks: bool = False):
    """Logits ``[B, S, V]`` (float32; with ``return_hidden`` the final
    normed hidden states) and the expert layers' routing, in layer order:
    ``{"expert_counts": [n_moe, E]}``, with ``picks`` also ``"experts"
    [n_moe, B * S, k]`` and ``"scores" [n_moe, B * S, E]`` (the float32
    sigmoid scores the choice was made from).  The whole sequence at
    once, expanded attention, no cache: the tests' path and the one the
    benchmark's driver reads the program's picks from."""
    B, S = tokens.shape
    h = embed_lookup(params["embed"], tokens, cfg.dtype)
    tables = rope_tables(jnp.broadcast_to(jnp.arange(S), (B, S)),
                         cfg.rope_theta, cfg.qk_rope_dim)
    mixer = _mixer(cfg, tables, mla_expanded_attend(cfg.qk_nope_dim, mesh))
    stats = []
    for (kind, _), stack in zip(layer_runs(cfg), params["runs"]):
        mlp = _mlp(kind, cfg, picks=picks)

        def body(h, lp):         # traced here, with this run's pair
            h, _, st = block(h, lp, mixer, mlp, cfg.rms_eps)
            return h, st

        h, st = lax.scan(body, h, stack)
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


def check_trainable(cfg: GlmMoeLiteConfig) -> None:
    """What :func:`horovod_tpu.models.llama.make_train_step` asks first."""
    raise NotImplementedError(
        "glm_moe_lite has no training objective here: the published model "
        "trains a multi-token prediction module (num_nextn_predict_layers) "
        "beside the next-token loss, a block that reads the last hidden "
        "state and the next token's embedding, which is neither written "
        "nor loaded; plain cross-entropy would train another model")


# -- serving: what models.llama's three steps ask a model for ------------------

def cache_rows(cfg: GlmMoeLiteConfig) -> tuple:
    """Per-token shape of each pool of the paged cache: ONE pool, a row
    of :attr:`GlmMoeLiteConfig.cache_row` values a token and layer."""
    return ((cfg.cache_row,),)


def pool_dims(cfg: GlmMoeLiteConfig) -> tuple:
    """Logical dims of a pool ``[L, NB, BS, row]``: nothing to split, the
    row is every head's."""
    return (None, None, None, None)


def shard_rules(cfg: GlmMoeLiteConfig, mesh: Optional[Mesh]):
    return None


def check_servable(cfg: GlmMoeLiteConfig, mesh: Optional[Mesh]) -> None:
    if mesh is not None and any(s > 1 for s in mesh.shape.values()):
        raise NotImplementedError(
            "glm_moe_lite is served whole on one chip: its leaves carry no "
            "logical dims to split over a mesh")


def serve_embed(embed, tokens, dtype):
    """The serving steps' embedding, a lookup: over 154,880 rows the
    one-hot product of :func:`~horovod_tpu.models.layers.embed_lookup`
    (which training wants for its backward and a sharded vocabulary for
    its partitioning, neither of which is here) is 7.8 TFLOP for a
    12,288-token prompt, as much as the layers it feeds, and reads the
    whole 0.63 GB table every decode tick."""
    with region("embed"):
        return jnp.take(embed, tokens, axis=0).astype(dtype)


def serve_runs(params: dict, cfg: GlmMoeLiteConfig, positions, mesh) -> list:
    """``[(stacked leaves, attend -> mixer, mlp)]``, a run of one layer
    kind each, for :func:`horovod_tpu.models.llama._serve_layers`."""
    tables = rope_tables(positions, cfg.rope_theta, cfg.qk_rope_dim)
    runs = []
    for (kind, n), stack in zip(layer_runs(cfg), params["runs"]):
        kw = {}
        if kind == "moe":
            # The experts stay out of the scan over layers, whole: a scan
            # hands its body a layer's slice, and XLA copies that slice
            # out (1.2 GB a layer) before the loop over tiles reads it.
            kw["stack"] = {k: stack[leaf].reshape(
                (-1,) + stack[leaf].shape[2:])
                for k, leaf in _EXPERT_LEAVES.items()}
            stack = {k: v for k, v in stack.items()
                     if k not in _EXPERT_LEAVES.values()}
            stack["expert_row"] = jnp.arange(n) * cfg.n_experts
        runs.append((stack, partial(_mixer, cfg, tables),
                     _mlp(kind, cfg, **kw)))
    return runs


def serve_stats(cfg: GlmMoeLiteConfig, extras: list) -> dict:
    """What a serving step returns beside its tokens, from the runs'
    stacked mlp extras: the expert layers' pairs by expert, ``[n_moe,
    E]``."""
    counts = [e["expert_counts"] for e in extras if e is not None]
    return {"expert_counts": jnp.concatenate(counts)} if counts else {}


def moe_layer_names(cfg: GlmMoeLiteConfig) -> list:
    """The 1-based numbers of the expert layers, as metric labels."""
    return [str(l) for l in range(cfg.first_k_dense + 1, cfg.n_layers + 1)]


def moe_experts_scored(cfg: GlmMoeLiteConfig) -> int:
    """How many experts an expert layer's router scores: all of which
    this chip holds (:func:`_moe_mlp`)."""
    return cfg.n_experts


def prefill_attend(cfg: GlmMoeLiteConfig, mesh, P: int):
    """``attend`` of the prompt prefill: the expanded form through the
    attention dispatch (the flash forward kernel at keys and values
    ``qk_dim`` and ``v_head_dim`` wide: a ``[P, P]`` mask and a float32
    score block of every head would be 12 GB at 12k tokens); what the
    layer leaves for the pool is its rows."""
    W = cfg.cache_row

    def attend(q, c, k_pe, w_kvb, li, state):
        k, v = mla_expand(c, k_pe, w_kvb, cfg.qk_nope_dim)
        return attention(q, k, v, mesh, True), (state,
                                                (mla_row(c, k_pe, W),))
    return attend


def prefill_path(cfg: GlmMoeLiteConfig, P: int) -> str:
    """The attention a ``P``-token prefill runs (``"flash"`` on the chip
    at the buckets a configuration should list)."""
    return attention_path((1, P, cfg.n_heads, cfg.qk_dim),
                          jnp.dtype(cfg.dtype).itemsize, None,
                          v_dim=cfg.v_head_dim)


def paged_kernel_ok(cfg: GlmMoeLiteConfig, mesh, block_size: int,
                    interpret: bool = False) -> bool:
    return interpret or FA.mla_paged_supported(
        block_size, cfg.cache_row, cfg.n_heads,
        jnp.dtype(cfg.dtype).itemsize)


def paged_attend(cfg: GlmMoeLiteConfig, mesh, tables, blk, off, mask,
                 last=None, interpret: bool = False):
    """``attend`` of the two paged steps: the layer's fresh rows go into
    page ``blk`` at ``off`` of the one pool, then the query, taken into
    the row's space, reads the table's window back: through the latent
    decode kernel where each stream's ``last`` position is given (one
    query a row), else through the contiguous gather under ``mask``; the
    result goes through the value up-projection."""
    C, nope, W = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.cache_row
    scale = cfg.qk_dim ** -0.5
    lengths = None if last is None else jnp.where(
        tables[:, 0] != 0, last + 1, 0)

    def attend(q, c, k_pe, w_kvb, li, state):
        pool = state[0].at[li, blk, off].set(
            mla_row(c, k_pe, W).reshape(blk.shape + (W,)))
        q_row = mla_absorb(q, w_kvb, nope, W)
        if lengths is not None:
            o = FA.mla_paged_attention(
                q_row[:, 0], pool, li, tables, lengths, v_dim=C, scale=scale,
                interpret=interpret)[:, None]
        else:
            rows = gather_blocks(pool[li], tables)[:, :, None]
            o = cached_attend(q_row, rows, rows[..., :C], mask, scale)
        o = jnp.einsum("bshc,chv->bshv", o, w_kvb[..., nope:])
        return o, ((pool,), None)

    return attend
