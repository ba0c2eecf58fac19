"""The compiled programs name their parts (``obs.trace.region``).

Every product, convolution and custom call of the train step and of the
serving steps lies in one region of the closed list, through ``scan``,
``looped``, ``checkpoint`` and autodiff; the remat'd step shows all three
phases; and a region is metadata alone: the compiled text with its
``metadata={...}`` dropped is that of the same step traced with ``region``
a null context.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu.models import glm_moe_lite as G, kimi_linear as K, llama
from horovod_tpu.obs import trace as obs_trace
from horovod_tpu.parallel import MeshConfig, build_mesh
from horovod_tpu.serving.engine import EngineConfig, ServingEngine

#: the closed list (ISSUE 37); ``hvd.exchange`` is the Horovod path's
REGIONS = {
    "hvd.embed", "hvd.block.mixer", "hvd.block.mlp", "hvd.moe.route",
    "hvd.moe.experts", "hvd.moe.combine", "hvd.moe.shared", "hvd.head",
    "hvd.loss", "hvd.renorm", "hvd.optim", "hvd.exchange"}
#: every module that writes a region
WRITERS = ("horovod_tpu.models.layers", "horovod_tpu.models.llama",
           "horovod_tpu.models.kimi_linear",
           "horovod_tpu.models.glm_moe_lite", "horovod_tpu.parallel.moe",
           "horovod_tpu.optim.distributed", "horovod_tpu.obs.trace")

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s(dot|convolution|custom-call)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HVD = re.compile(r"hvd\.[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)*")
# what partitioning leaves in the lowered text: annotations, not work
_ANNOTATION = re.compile(
    r'custom_call_target="(Sharding|SPMDFullToShardShape|'
    r'SPMDShardToFullShape|xla\.sdy\.\w+)"')


def _train_step(model, cfg):
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    tx = optax.adamw(1e-3)
    if model is K:
        tx = K.optimizer(tx)
    step = llama.make_train_step(cfg, mesh, tx, model=model)
    params = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
    return step.lower(params, state, batch)


def _serve_steps(model, cfg):
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, engine_cfg=EngineConfig(
        block_size=4, num_blocks=16, max_active=2, use_flash="never"))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return {"prefill": eng._prefill.lower(params, i32(1, 8), i32(1)),
            "decode": eng._decode.lower(params, eng.pools, i32(2), i32(2),
                                        i32(2, 4))}


def _lowered(case):
    kind, model, cfg = case
    if kind == "train":
        return {"train": _train_step(model, cfg)}
    return _serve_steps(model, cfg)


CASES = {
    "llama-train": ("train", llama, llama.LlamaConfig.tiny(remat=True)),
    "kimi-train": ("train", K, K.KimiLinearConfig.tiny(remat=True)),
    "llama-serve": ("serve", llama, llama.LlamaConfig.tiny()),
    "looped-serve": ("serve", llama, llama.LlamaConfig.tiny(loops=2)),
    "glm-serve": ("serve", G, G.GlmMoeLiteConfig.tiny()),
}


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_CALLEE = re.compile(
    r"(?:to_apply|body|condition|calls|true_computation|false_computation)="
    r"%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def _heavy(text):
    """``(instruction, opcode, op_name)`` of every product, convolution
    and custom call of an HLO text.  In the lowered text a function that
    is called (a kernel's interpreted body, ``jnp.take``) names its
    instructions from its own start, so the ``op_name`` given is the path
    down from the entry: the calling instructions' names before the
    instruction's own."""
    comp, rows, called_from = None, [], {}
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        name = _OP_NAME.search(line)
        op_name = name.group(1) if name else ""
        for one, many in _CALLEE.findall(line):
            for callee in [one] if one else re.findall(r"[\w.\-]+", many):
                called_from.setdefault(callee, (comp, op_name))
        m = _INSTR.match(line)
        if m and not _ANNOTATION.search(line):
            rows.append((comp, m.group(1), m.group(2), op_name))

    def path(comp):
        caller, op_name = called_from.get(comp, (None, ""))
        return (path(caller) + "/" if caller else "") + op_name

    for comp, instr, opcode, op_name in rows:
        yield instr, opcode, (path(comp) + "/" if op_name else "") + op_name


@functools.lru_cache(maxsize=None)
def _texts(case):
    """``{step: {"lowered", "compiled"}}``: the HLO text of a case's
    steps before and after the compiler, made once for the tests that
    read it."""
    return {step: {"lowered": low.compiler_ir(
        dialect="hlo").get_hlo_module().to_string(),
                   "compiled": low.compile().as_text()}
            for step, low in _lowered(CASES[case]).items()}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_product_lies_in_one_region_of_the_list(case):
    seen = set()
    for step, texts in _texts(case).items():
        for which, text in texts.items():
            heavy = list(_heavy(text))
            assert heavy, (case, step, which)
            for instr, opcode, op_name in heavy:
                if which == "compiled" and not op_name:
                    continue    # XLA's own (a product it split or merged)
                regions = _HVD.findall(op_name)
                assert regions and set(regions) <= REGIONS, (
                    case, step, which, instr, opcode, op_name)
                seen.add(regions[-1])
            unnamed = [i for i, _, n in heavy if not n]
            assert len(unnamed) <= len(heavy) // 4, (case, step, unnamed)
            if which == "compiled":     # regions that hold no product
                seen |= {r for n in _OP_NAME.findall(text)
                         for r in _HVD.findall(n)}
    assert seen <= REGIONS
    assert {"hvd.block.mixer", "hvd.block.mlp", "hvd.head"} <= seen
    if CASES[case][0] == "train":
        assert {"hvd.embed", "hvd.loss", "hvd.optim"} <= seen, seen
    if case in ("kimi-train", "glm-serve"):
        assert {"hvd.moe.route", "hvd.moe.experts", "hvd.moe.shared"} \
            <= seen, seen
    if case == "glm-serve":
        assert {"hvd.embed", "hvd.moe.combine"} <= seen, seen
    if case == "looped-serve":
        assert "hvd.renorm" in seen, seen


@pytest.mark.parametrize("case", ["llama-train", "kimi-train"])
def test_the_rematted_train_step_has_all_three_phases(case):
    text = _texts(case)["train"]["compiled"]
    phases = {}
    for _, _, op_name in _heavy(text):
        if not op_name:
            continue
        region = _HVD.findall(op_name)[-1]
        phase = "recompute" if "rematted_computation" in op_name else \
            "backward" if "transpose(" in op_name else "forward"
        phases.setdefault(region, set()).add(phase)
    for region in ("hvd.block.mixer", "hvd.block.mlp"):
        assert phases[region] == {"forward", "recompute", "backward"}, phases
    assert phases["hvd.head"] == {"forward", "backward"}, phases
    # the update and the add are named, and belong to no pass of autodiff
    named = {r for n in _OP_NAME.findall(text) for r in _HVD.findall(n)}
    assert "hvd.optim" in named and "hvd.loss" in named
    assert not any("transpose(" in n or "rematted_computation" in n
                   for n in _OP_NAME.findall(text) if "hvd.optim" in n)


def _stripped(text):
    return re.sub(r",? ?metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_region_changes_nothing_but_metadata(case, monkeypatch):
    texts = []
    for null in (False, True):     # one line traces both: the same frames
        if null:
            for module in WRITERS:
                monkeypatch.setattr(importlib.import_module(module), "region",
                                    lambda name: contextlib.nullcontext())
        jax.clear_caches()
        texts.append({step: low.compile().as_text()
                      for step, low in _lowered(CASES[case]).items()})
    jax.clear_caches()
    for step, text in texts[0].items():
        assert "hvd.block.mlp" in text and "hvd." not in _stripped(text)
        assert "hvd." not in texts[1][step], (case, step)
        assert _stripped(text) == _stripped(texts[1][step]), (case, step)


def test_region_is_the_one_caller_of_named_scope():
    import os
    root = os.path.dirname(os.path.abspath(obs_trace.__file__))
    root = os.path.dirname(root)                     # horovod_tpu/
    hits = []
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                src = open(os.path.join(d, f)).read()
                hits += [os.path.relpath(os.path.join(d, f), root)] * \
                    len(re.findall(r"\bnamed_scope\(", src))
    assert hits == [os.path.join("obs", "trace.py")], hits
    def double(x):
        with obs_trace.region("block.mlp"):
            return x * 2
    jaxpr = jax.make_jaxpr(double)(1.0)
    assert "hvd.block.mlp" in str(jaxpr.eqns[0].source_info.name_stack)
