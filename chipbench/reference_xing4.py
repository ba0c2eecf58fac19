"""The plain float32 reference of Xing4.0-29B-A4B (``xing4_0``), and the
comparison that decides its serving cell's ``correct``.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, a request at a time, nothing imported from the
program.  Routing, the expert layer, the SwiGLU and the judging of the
program's expert picks are GLM-4.7-Flash's (:mod:`chipbench.reference_glm`,
imported); the stream, its maps, the Sinkhorn rounds and YaRN are written
here from the equations.  Weights come from :mod:`chipbench.weights_xing4`
again, from the seed, one layer at a time.

With ``n = hc_mult`` and each sub-layer ``f`` (attention, then the MLP or
the expert layer) holding ``phi_f [n D, n^2 + 2n]``, ``b_f`` and
``alpha_f`` (3 values)::

    X = [e, e, ..., e]                              (the embedding, n times)
    x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)
    h_pre, h_post, h_res = split(x~ phi_f)          (n, n, n x n row-major)
    H_pre = sigmoid(alpha_0 h_pre + b_pre)
    H_post = 2 sigmoid(alpha_1 h_post + b_post)
    H_res = SK(exp(clamp(alpha_2 h_res + b_res, clamp_min, clamp_max)))
    X <- H_res X + H_post^T f(RMSNorm(sum_i H_pre,i X_i; g_f))
    h = sum_i sigmoid(alpha_head x~ phi_head + b_head)_i X_i  (after the last)
    logits = W_head RMSNorm(h; g_final)

``SK`` is ``hc_sinkhorn_iters`` rounds of dividing each row by its sum,
then each column by its sum, ``+ hc_eps`` in each divisor.  Attention is
GLM's latent attention in the expanded form, its rope columns rotated at
YaRN's frequencies (DeepSeek-V3's ``yarn_find_correction_range`` and
linear ramp, transcribed below) and its softmax scale ``(nope + rope)^-0.5
mscale(factor, mscale_all_dim)^2``, ``mscale(s, m) = 0.1 m ln s + 1``.

``quant="fp8"`` is the control of :mod:`chipbench.reference`: every
matmul operand rounded through float8_e4m3, the maps' product and the
streams' mixing included.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_xing4 as weights
from .reference import F32, _frozen, _mm, _rmsnorm
from .reference_glm import Q_BLOCK, _swiglu, compare_picks, expert_mlp


# -- YaRN ---------------------------------------------------------------------

def yarn_mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dims: dict) -> np.ndarray:
    """The rope columns' frequencies, float64, as DeepSeek-V3 computes
    them."""
    dim, base = dims["qk_rope"], dims["rope_theta"]
    corr = lambda rot: dim * math.log(
        dims["yarn_original"] / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(dims["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(dims["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / dims["yarn_factor"]
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1.0 - mask) + extra * mask


def softmax_scale(dims: dict) -> float:
    m = yarn_mscale(dims["yarn_factor"], dims["yarn_mscale_all_dim"])
    return (dims["qk_nope"] + dims["qk_rope"]) ** -0.5 * m * m


def _yarn_rope(x, dims: dict):
    """x [S, H, R], positions 0..S-1, half-split rotation."""
    S, _, R = x.shape
    freqs = jnp.asarray(yarn_inv_freq(dims), F32)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    k = yarn_mscale(dims["yarn_factor"], dims["yarn_mscale"]) / yarn_mscale(
        dims["yarn_factor"], dims["yarn_mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * k)[:, None, :], (jnp.sin(ang) * k)[:, None, :]
    x1, x2 = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# -- the arithmetic ---------------------------------------------------------

def attention(w: dict, x, dims: dict, quant=None):
    """Latent attention in its expanded form on one sequence x [S, D]."""
    eps = dims["rms_norm_eps"]
    C, nope = dims["kv_lora_rank"], dims["qk_nope"]
    S = x.shape[0]
    cq = _rmsnorm(_mm("sd,dr->sr", x, w["w_qa"], quant), w["q_norm"], eps)
    q = _mm("sr,rhk->shk", cq, w["w_qb"], quant)
    a = _mm("sd,dc->sc", x, w["w_kva"], quant)
    ckv = _rmsnorm(a[:, :C], w["kv_norm"], eps)
    k_pe = _yarn_rope(a[:, None, C:], dims)                  # [S, 1, R]
    kv = _mm("sc,chk->shk", ckv, w["w_kvb"], quant)
    H = kv.shape[1]
    q = jnp.concatenate([q[..., :nope], _yarn_rope(q[..., nope:], dims)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (S, H, k_pe.shape[-1]))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(dims)
    blk = min(Q_BLOCK, S)
    assert S % blk == 0, (S, blk)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        s = _mm("qhk,thk->hqt", qb, k, quant) * scale
        seen = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return _mm("hqt,thv->qhv", p, v, quant)

    o = jax.lax.map(block, jnp.arange(S // blk)).reshape(S, H, -1)
    return _mm("shv,hvd->sd", o, w["wo"], quant)


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds: rows, then columns, each divided by its sum."""
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def _normed(X, dims: dict):
    """x~: each token's n streams flattened, over their root mean square."""
    x = X.reshape(X.shape[0], -1)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + dims["hc_eps"])


def maps(w: dict, name: str, X, dims: dict, quant=None):
    """``(H_pre [S, n], H_post [S, n], H_res [S, n, n])`` of the sub-layer
    whose leaves start with ``name`` on the streams X [S, n, D]."""
    n = dims["hc_mult"]
    h = _mm("sk,km->sm", _normed(X, dims), w[name + "_phi"], quant)
    a, b = w[name + "_alpha"], w[name + "_bias"]
    pre = jax.nn.sigmoid(a[0] * h[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(a[2] * h[:, 2 * n:] + b[2 * n:],
                           *dims["hc_clamp"])).reshape(-1, n, n)
    return pre, post, sinkhorn(res, dims["hc_iters"], dims["hc_eps"])


def sublayer(w: dict, name: str, norm: str, f, X, dims: dict, quant=None):
    """``X <- H_res X + H_post^T f(RMSNorm(H_pre X))``; returns ``(X, what
    f returns beside its output)``."""
    pre, post, res = maps(w, name, X, dims, quant)
    u = _mm("sn,snd->sd", pre, X, quant)
    y, extra = f(_rmsnorm(u, w[norm], dims["rms_norm_eps"]))
    X = _mm("sij,sjd->sid", res, X, quant) + post[:, :, None] * y[:, None]
    return X, extra


def readout(outer: dict, X, dims: dict, quant=None):
    """The head's one mix of the streams X [S, n, D] -> [S, D]."""
    h = _mm("sk,km->sm", _normed(X, dims), outer["hc_head_phi"], quant)
    wts = jax.nn.sigmoid(outer["hc_head_alpha"][0] * h
                         + outer["hc_head_bias"])
    return _mm("sn,snd->sd", wts, X, quant)


def layer_fn(w: dict, X, dims: dict, dense: bool, quant=None):
    """One layer on one sequence's streams X [S, n, D] (float32): ``(X,
    picks)``, ``picks`` the expert layer's ``(experts, selection
    scores)``, None for a dense layer."""
    heavy = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
    w = {k: a if k in heavy else a.astype(F32) for k, a in w.items()}
    X, _ = sublayer(w, "hc_attn", "attn_norm",
                    lambda x: (attention(w, x, dims, quant), None), X, dims,
                    quant)
    if dense:
        mlp = lambda x: (_swiglu(x, w["w_gate"], w["w_up"], w["w_down"],
                                 quant), None)
    else:
        def mlp(x):
            y, experts, scores = expert_mlp(w, x, dims, quant)
            return y, (experts, scores)
    return sublayer(w, "hc_mlp", "mlp_norm", mlp, X, dims, quant)


def _head(outer: dict, X, dims: dict, quant=None):
    h = readout(outer, X, dims, quant)
    x = _rmsnorm(h, outer["final_norm"].astype(F32), dims["rms_norm_eps"])
    return _mm("sd,dv->sv", x, outer["lm_head"].astype(F32), quant)


def _streams(outer: dict, tokens, dims: dict):
    e = outer["embed"].astype(F32)[tokens]
    return jnp.broadcast_to(e[..., None, :], e.shape[:-1] + (
        dims["hc_mult"], e.shape[-1]))


def forward(seed_key, tokens, dims: dict, dtype, quant=None):
    """The whole model on one sequence of token ids [S] (S a multiple of
    :data:`Q_BLOCK` or shorter than it): ``(logits [S, V], experts [n_moe,
    S, k], scores [n_moe, S, E])``.  For the CPU tests; the chip's path
    is :func:`served_gaps`."""
    outer = jax.tree.map(lambda a: a.astype(F32),
                         weights.outer(seed_key, dims, dtype))
    X = _streams(outer, jnp.asarray(tokens), dims)
    picks = []
    for i in range(dims["n_layers"]):
        dense = weights.is_dense(dims, i)
        X, p = layer_fn(weights.layer(seed_key, i, dims, dtype, dense), X,
                        dims, dense, quant)
        if p is not None:
            picks.append(p)
    return (_head(outer, X, dims, quant),
            jnp.stack([e for e, _ in picks]), jnp.stack([s for _, s in picks]))


# -- serving: the gap of each served token under the reference ---------------

@functools.lru_cache(maxsize=None)
def _programs(fdims, dtype_name: str, quant):
    """The jitted blocks for one model shape (built once per process)."""
    dims, dtype = dict(fdims), jnp.dtype(dtype_name)
    make_layer = {dense: jax.jit(functools.partial(
        lambda key, i, dense: weights.layer(key, i, dims, dtype, dense),
        dense=dense)) for dense in (True, False)}
    make_outer = jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(F32), weights.outer(key, dims, dtype)))
    embed = jax.jit(lambda outer, tokens: _streams(outer, tokens, dims))

    def fwd(dense):
        return jax.jit(lambda w, rows: jax.lax.map(
            lambda X: layer_fn(w, X, dims, dense, quant), rows))

    @jax.jit
    def logits_at(outer, rows, pos):
        """Head logits at the listed positions of each row [K, n, V]."""
        return jax.lax.map(lambda rp: _head(outer, rp[0][rp[1]], dims, quant),
                           (rows, pos))

    return dict(make_layer=make_layer, make_outer=make_outer, embed=embed,
                fwd={True: fwd(True), False: fwd(False)},
                logits_at=logits_at)


def served_gaps(seed: int, dims: dict, dtype_name: str, samples: list,
                control: bool = False, picks=None) -> dict:
    """As :func:`chipbench.reference_glm.served_gaps`: one teacher-forced
    pass over prompt + served tokens per sample, a layer at a time over
    all samples; at every served position the gap by which the served
    token's reference logit lies below the reference's best
    (``served_gap``); the program's expert picks judged; with ``control``
    the fp8 reference's first choice and picks."""
    key = weights.root_key(seed)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)]) for p, t in samples]
    n_out = max(len(t) for _, t in samples)
    S = -(-max(len(s) for s in seqs) // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((len(seqs), S), np.int32)
    pos = np.zeros((len(seqs), n_out), np.int32)
    served = np.zeros((len(seqs), n_out), np.int32)
    valid = np.zeros((len(seqs), n_out), bool)
    for i, (p, t) in enumerate(samples):
        toks[i, :len(seqs[i])] = seqs[i]
        # served token j was picked from the logits at position P-1+j
        pos[i, :len(t)] = len(p) - 1 + np.arange(len(t))
        served[i, :len(t)] = t
        valid[i, :len(t)] = True

    def run(quant):
        pr = _programs(_frozen(dims), dtype_name, quant)
        outer = pr["make_outer"](key)
        rows = pr["embed"](outer, toks)
        experts, scores = [], []
        for i in range(dims["n_layers"]):
            dense = weights.is_dense(dims, i)
            rows, p = pr["fwd"][dense](pr["make_layer"][dense](key, i), rows)
            if p is not None:
                experts.append(np.asarray(p[0]))
                scores.append(np.asarray(p[1]))
        # [sample, n_moe, S, ...]
        return (pr["logits_at"](outer, rows, pos),
                np.stack(experts, 1), np.stack(scores, 1))

    def judge(experts_of):
        got = [compare_picks(ref_experts[i], ref_scores[i], experts_of(i),
                             len(seqs[i])) for i in range(len(seqs))]
        places = sum(len(s) for s in seqs)
        return {
            "agree_share": sum(g["agree_share"] * len(seqs[i])
                               for i, g in enumerate(got)) / places,
            "clean_places": sum(g["clean_places"] for g in got),
            "clean_flips": sum(g["clean_flips"] for g in got),
            "clean_flip_margin": max(g["clean_flip_margin"] for g in got)}

    ref, ref_experts, ref_scores = run(None)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    out = {"served_gap": np.asarray(gap)[valid],
           "n_tokens": int(valid.sum()), "checks": []}
    if picks is not None:
        out["picks"] = judge(lambda i: picks[i])
        out["checks"].append(("clean_flip_margin_max",
                              out["picks"]["clean_flip_margin"],
                              "clean_flip_margin_max"))
    if control:
        low, low_experts, _ = run("fp8")
        low = jnp.argmax(low, -1)
        cgap = best - jnp.take_along_axis(ref, low[..., None], -1)[..., 0]
        out["control_gap"] = np.asarray(cgap)[valid]
        out["control_picks"] = judge(lambda i: low_experts[i])
    return out
