"""The plain float32 reference of GLM-4.7-Flash (``glm4_moe_lite``), and
the comparison that decides its serving cell's ``correct``.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, a request at a time, the expanded form of latent
attention only, nothing imported from the program.  Weights come from
:mod:`chipbench.weights_glm` again, from the seed, one layer at a time.

The model, from the published ``config.json`` (what a key does not
itself state is listed under ``assumed`` in the configuration file):

    h = E[x]
    for layer l:  h = h + Attn_l(RMSNorm(h; g1));  h = h + MLP_l(RMSNorm(h; g2))
    logits = W_head RMSNorm(h; g_f)

    Attn(x) at position t:
      c_q = RMSNorm(x W_qa; g_q);  q = c_q W_qb, heads of nope + rope
      a = x W_kva;  c_kv = RMSNorm(a[:C]; g_kv);  k_pe = RoPE_t(a[C:])
      [k_nope_h, v_h] = c_kv W_kvb;  q_pe = RoPE_t(q_pe)
      k_h = [k_nope_h, k_pe];  causal softmax of q_h . k_h / sqrt(nope + rope)
      Attn = [sum_s p v_h] W_o

    MLP of layer 0: SwiGLU.  MLP of the others:
      s = sigmoid(x W_r);  the k largest of s + b;  w_i = scale s_i /
      (sum of the picked s + 1e-20);  y = sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x)

Attention runs in blocks of queries so that a 13k-token request fits; the
expert layer evaluates every expert on every token and weighs it by the
token's (mostly zero) routing weight: the per-token sum, as written.

**Routing is discrete.**  A bfloat16 hidden state and a float32 one can
pick another last expert on a near tie, and everything after differs by a
step, not by rounding.  So the reference also returns its picks and its
selection scores, and :func:`compare_picks` judges the program's picks
where they can be judged: a (layer, token) is *clean* if every pick the
program made for this and every earlier token in every earlier expert
layer agrees with the reference's, so that the two hidden states there
differ by rounding only.  A flipped pick at a clean place must be a near
tie in the reference's own scores (``clean_flip_margin``); after a flip
the two models run other experts, and their picks are reported
(``agree_share``) but not judged.

``quant="fp8"`` is the control of :mod:`chipbench.reference`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_glm as weights
from .reference import F32, _frozen, _mm, _rmsnorm, _rope

Q_BLOCK = 512


# -- the arithmetic ---------------------------------------------------------

def _swiglu(x, wg, wu, wd, quant):
    g = jax.nn.silu(_mm("sd,df->sf", x, wg, quant))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", x, wu, quant), wd, quant)


def attention(w: dict, x, dims: dict, quant=None):
    """Latent attention in its expanded form on one sequence x [S, D]."""
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    C, nope = dims["kv_lora_rank"], dims["qk_nope"]
    S = x.shape[0]
    cq = _rmsnorm(_mm("sd,dr->sr", x, w["w_qa"], quant), w["q_norm"], eps)
    q = _mm("sr,rhk->shk", cq, w["w_qb"], quant)
    a = _mm("sd,dc->sc", x, w["w_kva"], quant)
    ckv = _rmsnorm(a[:, :C], w["kv_norm"], eps)
    k_pe = _rope(a[:, None, C:], theta)                      # [S, 1, R]
    kv = _mm("sc,chk->shk", ckv, w["w_kvb"], quant)
    H = kv.shape[1]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (S, H, k_pe.shape[-1]))], -1)
    v = kv[..., nope:]
    scale = (nope + dims["qk_rope"]) ** -0.5
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


def route(scores, bias, dims: dict):
    """``(experts [S, k], weights [S, k])`` from the gate's activations:
    the k largest of ``scores + bias``, weighed by ``scores``."""
    _, experts = jax.lax.top_k(scores + bias, dims["experts_per_token"])
    w = jnp.take_along_axis(scores, experts, axis=-1)
    if dims["renormalize"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w * dims["routed_scale"]


def expert_mlp(w: dict, x, dims: dict, quant=None):
    """The expert layer on x [S, D]: ``(y, experts [S, k], scores [S,
    E])``.  Every expert on every token, times the token's weight for it
    (zero for all but k)."""
    S, E = x.shape[0], dims["n_experts"]
    scores = jax.nn.sigmoid(_mm("sd,de->se", x, w["router"], quant))
    experts, wts = route(scores, w["router_bias"], dims)
    full = jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], experts].set(wts)

    def one(y, ew):
        wg, wu, wd, col = ew
        return y + col[:, None] * _swiglu(
            x, wg.astype(F32), wu.astype(F32), wd.astype(F32), quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["e_gate"], w["e_up"], w["e_down"], full.T))
    y = y + _swiglu(x, w["s_gate"].astype(F32), w["s_up"].astype(F32),
                    w["s_down"].astype(F32), quant)
    return y, experts.astype(jnp.int32), scores + w["router_bias"]


def layer_fn(w: dict, h, dims: dict, dense: bool, quant=None):
    """One layer on one sequence h [S, D] (float32): ``(h, picks)``,
    ``picks`` the expert layer's ``(experts, selection scores)``, None for
    the dense layer."""
    heavy = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
    w = {k: a if k in heavy else a.astype(F32) for k, a in w.items()}
    eps = dims["rms_norm_eps"]
    h = h + attention(w, _rmsnorm(h, w["attn_norm"], eps), dims, quant)
    x = _rmsnorm(h, w["mlp_norm"], eps)
    if dense:
        return h + _swiglu(x, w["w_gate"], w["w_up"], w["w_down"],
                           quant), None
    y, experts, scores = expert_mlp(w, x, dims, quant)
    return h + y, (experts, scores)


def forward(seed_key, tokens, dims: dict, dtype, quant=None):
    """The whole model on one sequence of token ids [S] (S a multiple of
    :data:`Q_BLOCK` or shorter than it): ``(logits [S, V], experts [n_moe,
    S, k], scores [n_moe, S, E])``.  For the CPU tests; the chip's path
    is :func:`served_gaps`, which never holds all the logits."""
    outer = weights.outer(seed_key, dims, dtype)
    h = outer["embed"].astype(F32)[jnp.asarray(tokens)]
    picks = []
    for i in range(dims["n_layers"]):
        dense = weights.is_dense(dims, i)
        h, p = layer_fn(weights.layer(seed_key, i, dims, dtype, dense), h,
                        dims, dense, quant)
        if p is not None:
            picks.append(p)
    x = _rmsnorm(h, outer["final_norm"].astype(F32), dims["rms_norm_eps"])
    logits = _mm("sd,dv->sv", x, outer["lm_head"].astype(F32), quant)
    return (logits, jnp.stack([e for e, _ in picks]),
            jnp.stack([s for _, s in picks]))


# -- the picks ----------------------------------------------------------------

def compare_picks(ref_experts, ref_scores, experts, n_valid: int) -> dict:
    """The program's picks ``experts [n_moe, S, k]`` against the
    reference's and its selection scores ``[n_moe, S, E]``, over the first
    ``n_valid`` positions.  ``agree_share``: (layer, token) pairs whose
    picked sets are equal, over all.  ``clean_flips``: disagreeing pairs
    at clean places (see the module's docstring); ``clean_flip_margin``:
    over those, the most by which a pick of the program lies under the
    reference's last pick in the reference's scores (0 with no clean
    flip).  ``clean_places``: how many places could be judged."""
    r = np.sort(np.asarray(ref_experts)[:, :n_valid], -1)
    p = np.sort(np.asarray(experts)[:, :n_valid], -1)
    s = np.asarray(ref_scores, np.float64)[:, :n_valid]
    differ = (r != p).any(-1)                              # [n_moe, S]
    first = np.where(differ.any(1), differ.argmax(1), n_valid)
    # clean at layer l: before the first flip of every earlier layer
    before = np.minimum.accumulate(np.concatenate([[n_valid], first[:-1]]))
    clean = np.arange(n_valid)[None, :] < before[:, None]
    last = np.take_along_axis(s, r, -1).min(-1)
    worst = np.take_along_axis(s, p, -1).min(-1)
    flips = differ & clean
    return {"agree_share": float(1.0 - differ.mean()),
            "clean_places": int(clean.sum()),
            "clean_flips": int(flips.sum()),
            "clean_flip_margin": float((last - worst)[flips].max(initial=0.0))}


# -- serving: the gap of each served token under the reference ---------------

@functools.lru_cache(maxsize=None)
def _programs(fdims, dtype_name: str, quant):
    """The jitted blocks for one model shape (built once per process)."""
    dims, dtype = dict(fdims), jnp.dtype(dtype_name)
    make_layer = {dense: jax.jit(functools.partial(
        lambda key, i, dense: weights.layer(key, i, dims, dtype, dense),
        dense=dense)) for dense in (True, False)}
    make_outer = jax.jit(lambda key: weights.outer(key, dims, dtype))

    @jax.jit
    def embed(w_embed, tokens):
        return w_embed.astype(F32)[tokens]

    def fwd(dense):
        return jax.jit(lambda w, rows: jax.lax.map(
            lambda h: layer_fn(w, h, dims, dense, quant), rows))

    @jax.jit
    def logits_at(outer, rows, pos):
        """Head logits at the listed positions of each row [K, n, V]."""
        head = outer["lm_head"].astype(F32)
        norm = outer["final_norm"].astype(F32)
        return jax.lax.map(
            lambda hp: _mm("sd,dv->sv", _rmsnorm(
                hp[0][hp[1]], norm, dims["rms_norm_eps"]), head, quant),
            (rows, pos))

    return dict(make_layer=make_layer, make_outer=make_outer, embed=embed,
                fwd={True: fwd(True), False: fwd(False)},
                logits_at=logits_at)


def served_gaps(seed: int, dims: dict, dtype_name: str, samples: list,
                control: bool = False, picks=None) -> dict:
    """``samples``: (prompt ids, served ids) pairs.  One teacher-forced
    pass over prompt + served tokens per sample; at every served position
    the gap by which the served token's reference logit lies below the
    reference's best (``served_gap``).  ``picks``: for each sample the
    program's experts ``[n_moe, >= len, k]`` on the same tokens, judged
    by :func:`compare_picks` (``checks`` lists what a driver holds to a
    limit).  With ``control`` also the gap of the token the fp8 reference
    puts first, and the fp8 reference's own picks judged the same way."""
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
        rows = pr["embed"](outer["embed"], toks)
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
        places = sum(len(seqs[i]) for i in range(len(seqs)))
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
