"""DLRM — BASELINE config 5: the sparse-embedding alltoall workload
(† ``hvd.alltoall`` / DLRM-style model-parallel embedding exchange; the
reference added alltoall in v0.20 precisely for this pattern).

Architecture (Naumov et al., arXiv:1906.00091): dense features → bottom
MLP; categorical features → embedding lookups; pairwise dot-product feature
interaction; top MLP → CTR logit.

TPU-native parallelism: embedding *tables* are sharded across devices
(model parallel — each device owns ``n_tables / n_dev`` full tables) while
the *batch* is data-parallel.  Each step, every device looks up its tables
for the whole global batch, then one ``all_to_all`` re-shards the result
from table-major to batch-major — the exact exchange ``hvd.alltoall``
exists for.  This lives in :func:`sharded_embedding_lookup` on the engine's
alltoall verb, with a shard_map fast path inside compiled steps.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class DlrmConfig:
    n_dense: int = 13
    n_sparse: int = 26            # number of categorical tables
    vocab_per_table: int = 1000
    embed_dim: int = 16
    bottom_mlp: Sequence[int] = (64, 32, 16)
    top_mlp: Sequence[int] = (64, 32, 1)
    dtype: Any = jnp.float32

    @staticmethod
    def tiny(**kw) -> "DlrmConfig":
        base = dict(n_dense=4, n_sparse=8, vocab_per_table=64, embed_dim=8,
                    bottom_mlp=(16, 8), top_mlp=(16, 1))
        base.update(kw)
        return DlrmConfig(**base)


class MLP(nn.Module):
    sizes: Sequence[int]
    dtype: Any = jnp.float32
    final_activation: bool = False

    @nn.compact
    def __call__(self, x):
        for i, n in enumerate(self.sizes):
            x = nn.Dense(n, dtype=self.dtype)(x)
            if i < len(self.sizes) - 1 or self.final_activation:
                x = nn.relu(x)
        return x


def interact_features(dense_emb: jax.Array, sparse_emb: jax.Array
                      ) -> jax.Array:
    """Pairwise dot-product interaction (arXiv:1906.00091 §2).

    dense_emb: [B, D]; sparse_emb: [B, T, D] → [B, D + T*(T+1)//2].
    """
    B, T, D = sparse_emb.shape
    all_emb = jnp.concatenate([dense_emb[:, None, :], sparse_emb], axis=1)
    inter = jnp.einsum("bid,bjd->bij", all_emb, all_emb)
    iu, ju = np.triu_indices(T + 1, k=1)
    flat = inter[:, iu, ju]
    return jnp.concatenate([dense_emb, flat], axis=1)


class DlrmDense(nn.Module):
    """The dense (data-parallel) half: bottom MLP, interaction, top MLP.

    Embedding lookups happen outside (they're the model-parallel half).
    """

    cfg: DlrmConfig

    @nn.compact
    def __call__(self, dense_features, sparse_embeddings):
        cfg = self.cfg
        bot = MLP(cfg.bottom_mlp, dtype=cfg.dtype,
                  final_activation=True)(dense_features)
        assert bot.shape[-1] == cfg.embed_dim, \
            "bottom MLP must end at embed_dim for interaction"
        z = interact_features(bot, sparse_embeddings)
        return MLP(cfg.top_mlp, dtype=cfg.dtype)(z)[..., 0]


def init_embedding_tables(cfg: DlrmConfig, key: jax.Array) -> jax.Array:
    """[n_sparse, vocab, dim] — leading dim shards across devices."""
    return (jax.random.normal(
        key, (cfg.n_sparse, cfg.vocab_per_table, cfg.embed_dim), jnp.float32)
        * 0.05).astype(cfg.dtype)


def sharded_embedding_lookup_local(tables: jax.Array, indices: jax.Array, *,
                                   axis_name: str = "hvd") -> jax.Array:
    """Inside a mapped context: tables local [T/n, V, D]; indices local
    (batch-sharded) [b, T] for ALL T tables.

    Exchange 1 (all_to_all): ship each batch shard's indices for my tables
    to me — indices are batch-sharded, tables are table-sharded, so the
    lookup needs a transpose of the sharding, which is exactly one
    all_to_all each way († DLRM's butterfly shuffle on ``hvd.alltoall``).
    """
    n = axis_size(axis_name)
    b, T = indices.shape
    t_local = tables.shape[0]
    # [b, T] -> [n, b, T/n]: group index columns by owning device.
    idx_by_owner = indices.reshape(b, n, t_local).transpose(1, 0, 2)
    # all_to_all: device i receives every batch-shard's columns for its
    # tables: [n, b, t_local] with leading dim = source batch shard.
    recv = lax.all_to_all(idx_by_owner, axis_name, split_axis=0,
                          concat_axis=0, tiled=False)
    #

    # Lookup my tables for the full global batch: [n*b, t_local, D].
    flat_idx = recv.reshape(n * b, t_local)
    looked = jnp.take_along_axis(
        tables[None, :, :, :],  # [1, t_local, V, D]
        flat_idx[:, :, None, None].astype(jnp.int32), axis=2
    )[:, :, 0, :]  # [n*b, t_local, D]
    # Exchange 2 (reverse): return embeddings to the batch shards.
    send_back = looked.reshape(n, b, t_local, -1)
    recv_back = lax.all_to_all(send_back, axis_name, split_axis=0,
                               concat_axis=0, tiled=False)
    # [n, b, t_local, D] with leading dim = table owner -> [b, T, D].
    return recv_back.transpose(1, 0, 2, 3).reshape(b, T, -1)


def sharded_embedding_lookup(tables: jax.Array, indices: jax.Array,
                             mesh: Mesh, *, axis_name: str = "hvd"
                             ) -> jax.Array:
    """Standalone entry: tables [T, V, D] sharded over axis 0; indices
    [B, T] batch-sharded over axis 0; returns [B, T, D] batch-sharded."""
    fn = shard_map(
        partial(sharded_embedding_lookup_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False)
    return jax.jit(fn)(tables, indices)


def synthetic_batch(cfg: DlrmConfig, batch: int, seed: int = 0) -> dict:
    rng = np.random.RandomState(seed)
    return {
        "dense": jnp.asarray(rng.rand(batch, cfg.n_dense), jnp.float32),
        "sparse": jnp.asarray(
            rng.randint(0, cfg.vocab_per_table, size=(batch, cfg.n_sparse)),
            jnp.int32),
        "label": jnp.asarray(rng.randint(0, 2, size=(batch,)), jnp.float32),
    }
