"""Model zoo: ResNet, BERT, DLRM — forward correctness + data-parallel
training (the BASELINE configs 2, 3, 5).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import bert as bert_mod
from horovod_tpu.models import dlrm as dlrm_mod
from horovod_tpu.models.resnet import resnet18_thin, resnet50

N = 8


# ---------------------------------------------------------------------------
# ResNet
# ---------------------------------------------------------------------------

def test_resnet50_builds():
    model = resnet50(dtype=jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 224, 224, 3)), train=False))
    n_params = sum(np.prod(x.shape) for x in
                   jax.tree.leaves(variables["params"]))
    # ResNet-50 has ~25.6M params; sanity window.
    assert 24e6 < n_params < 27e6, n_params


def test_resnet_thin_trains_dp():
    model = resnet18_thin(num_classes=10, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    x = rng.rand(16, 32, 32, 3).astype(np.float32)
    y = rng.randint(0, 10, size=(16,))
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = hvd.DistributedOptimizer(optax.sgd(0.05))
    opt_state = tx.init(params)
    mesh = hvd.mesh()

    def step(params, batch_stats, opt_state, xb, yb):
        def loss_fn(p):
            logits, new_vars = model.apply(
                {"params": p, "batch_stats": batch_stats}, xb,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return loss, new_vars["batch_stats"]
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        params2 = optax.apply_updates(params, updates)
        # batch_stats averaged across replicas (cross-replica running stats).
        new_bs = jax.tree.map(lambda a: jax.lax.pmean(a, "hvd"), new_bs)
        return params2, new_bs, opt_state2, jax.lax.pmean(loss, "hvd")

    sharded = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P()), check_vma=False))

    xb = jax.device_put(x, NamedSharding(mesh, P("hvd")))
    yb = jax.device_put(y, NamedSharding(mesh, P("hvd")))
    losses = []
    for _ in range(6):
        params, batch_stats, opt_state, loss = sharded(
            params, batch_stats, opt_state, xb, yb)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_resnet_syncbn_matches_global_bn():
    """SyncBatchNorm via axis_name: per-shard BN statistics psum'd across
    the axis must equal single-device BN over the full batch
    († sync_batch_norm.py semantics)."""
    model_sync = resnet18_thin(num_classes=4, dtype=jnp.float32,
                               axis_name="hvd")
    model_plain = resnet18_thin(num_classes=4, dtype=jnp.float32)
    rng = np.random.RandomState(1)
    x = rng.rand(16, 16, 16, 3).astype(np.float32)
    variables = model_plain.init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 16, 16, 3)), train=False)
    mesh = hvd.mesh()

    ref, _ = model_plain.apply(variables, jnp.asarray(x), train=True,
                               mutable=["batch_stats"])

    def fwd(v, xb):
        out, _ = model_sync.apply(v, xb, train=True, mutable=["batch_stats"])
        return out

    sharded = jax.jit(shard_map(
        fwd, mesh=mesh, in_specs=(P(), P("hvd")), out_specs=P("hvd"),
        check_vma=False))
    got = sharded(variables, jax.device_put(x, NamedSharding(mesh, P("hvd"))))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# BERT
# ---------------------------------------------------------------------------

def test_bert_large_param_count():
    cfg = bert_mod.BertConfig.bert_large()
    model = bert_mod.Bert(cfg)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))
    n_params = sum(np.prod(x.shape) for x in jax.tree.leaves(variables))
    # BERT-Large ≈ 335M (tied MLM head).
    assert 300e6 < n_params < 360e6, n_params


def test_bert_mlm_trains_dp():
    cfg = bert_mod.BertConfig.tiny()
    model = bert_mod.Bert(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    batch = bert_mod.synthetic_mlm_batch(cfg, batch=16, seq=32)
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = tx.init(params)
    mesh = hvd.mesh()

    def step(params, opt_state, tokens, labels):
        def loss_fn(p):
            return bert_mod.mlm_loss(
                p, {"tokens": tokens, "labels": labels}, model)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state2,
                jax.lax.pmean(loss, "hvd"))

    sharded = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False))
    tok = jax.device_put(batch["tokens"], NamedSharding(mesh, P("hvd")))
    lab = jax.device_put(batch["labels"], NamedSharding(mesh, P("hvd")))
    losses = []
    for _ in range(10):
        params, opt_state, loss = sharded(params, opt_state, tok, lab)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# DLRM
# ---------------------------------------------------------------------------

def test_dlrm_sharded_embedding_matches_dense_lookup():
    cfg = dlrm_mod.DlrmConfig.tiny()
    mesh = hvd.mesh()
    tables = dlrm_mod.init_embedding_tables(cfg, jax.random.PRNGKey(0))
    batch = dlrm_mod.synthetic_batch(cfg, batch=16)
    # Oracle: direct gather.
    idx = np.asarray(batch["sparse"])
    expected = np.stack([np.asarray(tables)[t, idx[:, t]]
                         for t in range(cfg.n_sparse)], axis=1)
    got = dlrm_mod.sharded_embedding_lookup(
        jax.device_put(tables, NamedSharding(mesh, P("hvd"))),
        jax.device_put(batch["sparse"], NamedSharding(mesh, P("hvd"))),
        mesh)
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-6)


def test_dlrm_trains_end_to_end():
    cfg = dlrm_mod.DlrmConfig.tiny()
    mesh = hvd.mesh()
    dense_model = dlrm_mod.DlrmDense(cfg)
    batch = dlrm_mod.synthetic_batch(cfg, batch=16)
    tables = dlrm_mod.init_embedding_tables(cfg, jax.random.PRNGKey(1))
    demb0 = np.zeros((1, cfg.n_sparse, cfg.embed_dim), np.float32)
    params = dense_model.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, cfg.n_dense)), jnp.asarray(demb0))
    tx = optax.adam(1e-2)
    opt_state = tx.init((params, tables))

    t_sh = NamedSharding(mesh, P("hvd"))
    b_sh = NamedSharding(mesh, P("hvd"))
    repl = NamedSharding(mesh, P())

    def step(params, tables, opt_state, dense, sparse, label):
        def loss_fn(pt):
            p, tb = pt
            # Embedding exchange via shard_map nested under jit.
            from functools import partial
            emb = shard_map(
                partial(dlrm_mod.sharded_embedding_lookup_local,
                        axis_name="hvd"),
                mesh=mesh, in_specs=(P("hvd"), P("hvd")),
                out_specs=P("hvd"), check_vma=False)(tb, sparse)
            logit = dense_model.apply(p, dense, emb)
            return optax.sigmoid_binary_cross_entropy(logit, label).mean()
        loss, grads = jax.value_and_grad(loss_fn)((params, tables))
        updates, opt_state2 = tx.update(grads, opt_state, (params, tables))
        params2, tables2 = optax.apply_updates((params, tables), updates)
        return params2, tables2, opt_state2, loss

    jstep = jax.jit(step,
                    in_shardings=(repl, t_sh, None, b_sh, b_sh, b_sh),
                    out_shardings=(repl, t_sh, None, repl))
    dense = jax.device_put(batch["dense"], b_sh)
    sparse = jax.device_put(batch["sparse"], b_sh)
    label = jax.device_put(batch["label"], b_sh)
    tables = jax.device_put(tables, t_sh)
    losses = []
    for _ in range(15):
        params, tables, opt_state, loss = jstep(
            params, tables, opt_state, dense, sparse, label)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_dlrm_interaction_shape():
    cfg = dlrm_mod.DlrmConfig.tiny()
    B, T, D = 4, cfg.n_sparse, cfg.embed_dim
    out = dlrm_mod.interact_features(
        jnp.zeros((B, D)), jnp.zeros((B, T, D)))
    assert out.shape == (B, D + (T + 1) * T // 2)


# ---------------------------------------------------------------------------
# The import graph: models/ points down
# ---------------------------------------------------------------------------

def _imports_of(path):
    """Absolute module names a file imports, function-level imports
    included (a lazy ``from ..serving import x`` is still an arrow up)."""
    import ast
    pkg = ["horovod_tpu", "models"]
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


def test_model_files_import_nothing_above_them():
    """No file under ``models/`` imports ``serving`` or ``context``, at
    module level or inside a function, and ``kimi_linear`` does not import
    ``llama``: what they share is in ``models/layers.py``.  (``context``
    is read from the source, not from ``sys.modules``: the package's own
    ``__init__`` imports it before any model.)"""
    import pathlib
    models = pathlib.Path(hvd.__file__).parent / "models"
    for path in sorted(models.glob("*.py")):
        for mod in _imports_of(path):
            assert not mod.startswith(("horovod_tpu.serving",
                                       "horovod_tpu.context")), \
                f"{path.name} imports {mod}"
    kimi = _imports_of(models / "kimi_linear.py")
    assert "horovod_tpu.models.llama" not in kimi, kimi
    assert "horovod_tpu.models.layers" in kimi


def test_importing_the_models_leaves_serving_and_each_other_out():
    """In a fresh interpreter: ``kimi_linear`` alone does not bring
    ``llama`` in, and neither brings ``horovod_tpu.serving``."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "import horovod_tpu.models.kimi_linear\n"
        "assert 'horovod_tpu.models.llama' not in sys.modules\n"
        "import horovod_tpu.models.llama\n"
        "up = [m for m in sys.modules if m.startswith('horovod_tpu.serving')]\n"
        "assert not up, up\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
