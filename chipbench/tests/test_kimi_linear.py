"""Tests of what the Kimi-Linear cell adds to the yardstick; on the CPU.

The comparison at a tiny size (a sound run passes; the fp8 control and
each planted fault fail), ``costs_kimi_linear`` against hand counts, and
the manifest's new pieces found by name.  Test names here differ from
those of the other files in this directory: ``tests/test_chipbench.py``
loads them all into one namespace.
"""

from __future__ import annotations

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import costs_kimi_linear as kcosts              # noqa: E402
from chipbench import run as runmod                            # noqa: E402
from chipbench import weights_kimi_linear as kweights          # noqa: E402

KIMI_BENCH = os.path.join(ROOT, "chipbench")
KIMI_MANIFEST = runmod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
KIMI_TINY = runmod.load_json(os.path.join(
    KIMI_BENCH, "testdata", "tiny_kimi", "BENCHMARK.json"))
KIMI_CELL = "kimi-linear-train-s8192"
KIMI_CONFIG = runmod.load_json(os.path.join(
    KIMI_BENCH, "configs", "kimi-linear-48b-a3b-train-ep16-l5.json"))
KIMI_DIMS = kweights.dims_of(KIMI_CONFIG)
KIMI_CONTROLS = ("fp8", "half_batch", "no_decay", "no_shared",
                 "absent_added")


# -- the comparison, at a size a test can hold -------------------------------------

@pytest.fixture(scope="module")
def kimi_tiny_run():
    """One run of the tiny cell (experts 4 to 7 of 16 held) with the
    control and the planted faults read."""
    return runmod.execute(KIMI_TINY, "tiny-kimi", 2**31 + 13, 0.2, False,
                          require_tpu=False, control=True)


def test_kimi_tiny_sound_run_is_correct(kimi_tiny_run):
    out = kimi_tiny_run
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert set(out["controls"]) == set(KIMI_CONTROLS)


@pytest.mark.parametrize("plant", KIMI_CONTROLS)
def test_kimi_tiny_control_and_faults_come_out_not_correct(kimi_tiny_run,
                                                           plant):
    lim = {k: v["limit"] for k, v in kimi_tiny_run["checks"].items()}
    reading = kimi_tiny_run["controls"][plant]
    assert any(reading[k] > lim[k] for k in reading if k in lim), reading


def test_kimi_state_unchanged_reads_one():
    """The fault that needs no run: a program that hands its state back
    reads a change of 0, a gap of 1."""
    from chipbench import reference
    ref = {"loss": [1.0], "grad_norm": {"a": 1.0, "b": 2.0},
           "delta_norm": {"a": 0.5, "b": 0.25}}
    still = dict(ref, delta_norm={"a": 0.0, "b": 0.0})
    assert reference.compare_training(still, ref)["delta_norm_gap"] == 1.0


def test_kimi_change_gap_is_read_against_rounded_weights_too():
    """A first-layer leaf whose change is a fifth larger in the program,
    as in the reference's copy kept in the configuration's dtype: the gap
    against float32 reads it, the gap against the rounded copy reads 0."""
    from chipbench import reference_kimi_linear as kref
    ref = {"loss": [1.0], "grad_norm": {"L0.a": 1.0, "L1.a": 1.0},
           "delta_norm": {"L0.a": 1.0, "L1.a": 1.0},
           "delta_norm_rounded": {"L0.a": 1.2}}
    program = dict(ref, delta_norm={"L0.a": 1.2, "L1.a": 1.0})
    got = kref.compare_training(program, ref)
    assert got["delta_norm_gap"] == pytest.approx(0.2)
    assert got["delta_norm_gap_first"] == pytest.approx(0.2)
    assert got["delta_norm_gap_first_rounded"] == 0.0


# -- costs, against a hand count for one layer of each kind ------------------------

def test_kimi_costs_weights_of_each_mixer_and_expert():
    # q, k, v, o 4 x 2304 x 4096; two gates 2 x (2304 x 128 + 128 x 4096);
    # beta 2304 x 32 (convs, A_log, dt_bias and gains are not matrices)
    assert kcosts.kda_mixer_weights(KIMI_DIMS) == \
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    # q 2304 x 6144; kv_a 2304 x 576; kv_b 512 x 8192; o 4096 x 2304
    assert kcosts.mla_mixer_weights(KIMI_DIMS) == \
        2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert kcosts.expert_weights(KIMI_DIMS) == 3 * 2304 * 1024 == 7_077_888


def test_kimi_costs_forward_flops_by_part():
    rows, seq, pairs = 4, 8192, 65536.0
    tokens = rows * seq
    f = kcosts.forward_flops(KIMI_DIMS, rows, seq, pairs)
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    # four KDA layers: the matrices, the three width-4 convs, the core
    assert f["kda_projections"] == 4 * tokens * (2 * kda + 2 * 3 * 4 * 4096)
    assert f["kda_core"] == 4 * tokens * 7 * 32 * 128 * 128
    # one MLA layer: QK^T over 192 and PV over 128, causal
    assert f["mla_attention"] == 2 * 32 * (192 + 128) * \
        rows * seq * (seq + 1) // 2
    assert f["dense_mlp"] == tokens * 2 * 3 * 2304 * 9216      # layer 1 only
    assert f["shared_experts"] == 4 * tokens * 2 * 7_077_888
    assert f["routers"] == 4 * tokens * 2 * 2304 * 256
    # routed experts from the pairs held, not from tokens x 8
    assert f["routed_experts"] == 2 * 7_077_888 * pairs
    assert f["head"] == tokens * 2 * 2304 * 20480
    assert kcosts.train_flops_per_step(KIMI_DIMS, rows, seq, pairs) == \
        3 * sum(f.values())
    # about 790 M a token forward, as the issue reckoned
    assert 7.6e8 < sum(f.values()) / tokens < 8.1e8


def test_kimi_costs_kda_core_call_is_bound_by_its_bytes():
    flops, byts = kcosts.kda_core_cost(KIMI_DIMS, 4, 8192)
    assert flops == 4 * 8192 * 7 * 32 * 128 * 128
    # per token and head: q, k, v, o in bf16, g in float32, beta
    assert byts == 4 * 8192 * 32 * (128 * (4 * 2 + 4) + 4)
    assert byts / 819e9 > flops / 197e12


def test_kimi_costs_flash_calls_at_keys_wider_than_values():
    # per (query, key) pair and head: forward QK^T over 192 and PV over
    # 128; dq two products over 192 and one over 128; dk/dv two of each
    attended = 4 * 8192 * (8192 + 1) // 2
    f = kcosts.flash_flops(KIMI_DIMS, 4, 8192)
    assert f == {"fwd": 2 * 32 * (192 + 128) * attended,
                 "dq": 2 * 32 * (2 * 192 + 128) * attended,
                 "dkv": 2 * 32 * (2 * 192 + 2 * 128) * attended}
    # the forward is the step's required attention work
    assert f["fwd"] == kcosts.forward_flops(
        KIMI_DIMS, 4, 8192, 0.0)["mla_attention"]


def test_kimi_flash_roofline_on_a_hand_trace():
    """One call of each flash kernel of known length; an op that takes a
    kernel's result is not counted; nothing to read leaves it out."""
    from chipbench.reducers import flash_attention_roofline_kimi as fr
    parts = runmod.load_cell(KIMI_MANIFEST, KIMI_CELL)
    spec = {s["name"]: s for s in parts["layer_metrics"]}
    cell = dict(parts, peaks={"flops_per_s": 197e12},
                spec=spec["flash_attention_roofline.kimi"])
    call = ("%{0}.2 = bf16[128,8192,128]{{2,1,0}} custom-call(%a), "
            "custom_call_target=\"tpu_custom_call\"")
    names = ("checkpoint_hvd_flash_fwd", "hvd_flash_bwd_dq",
             "transpose_jvp_hvd_flash_bwd_dkv_")
    user = "%fusion.1 = bf16[128,8192,128]{2,1,0} fusion(%hvd_flash_fwd.2)"
    red = {"ops": [(0, call.format(n), 0.1 * i, 0.03)
                   for i, n in enumerate(names)] + [(0, user, 0.5, 0.4)],
           "lo": 0.0, "hi": 1.0, "devices": [0]}
    f = kcosts.flash_flops(KIMI_DIMS, 4, 8192)
    assert fr.reduce(red, {}, cell) == pytest.approx(
        100 * sum(f.values()) / 197e12 / 0.09)
    assert fr.reduce(dict(red, ops=red["ops"][3:]), {}, cell) is None


# -- the manifest's new pieces ------------------------------------------------------

def test_kimi_cell_parts_and_reducers_are_found_by_name():
    parts = runmod.load_cell(KIMI_MANIFEST, KIMI_CELL)
    assert parts["traffic"]["driver"] == "train_kimi_linear"
    importlib.import_module("chipbench.drivers." + parts["traffic"]["driver"])
    names = {m["name"] for m in parts["layer_metrics"]}
    assert {"train_step_mfu.kimi", "kda_roofline.kimi",
            "kda_kernel_share.kimi", "moe_load_max_over_mean.kimi",
            "flash_attention_roofline.kimi", "flash_kernel_share", "compile_s",
            "compiles_in_window"} <= names
    assert not {"train_step_mfu", "flash_attention_roofline"} & names
    for spec in parts["layer_metrics"]:
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce)
    assert {m["name"] for m in parts["end_to_end"]} == \
        {"train_tokens_per_s_per_chip", "setup_s"}


def test_kimi_config_keeps_every_published_number():
    """Every top-level number of the published config.json under its key,
    but those listed in ``reduced``; nested groups whole but the two
    layer lists; no width among the cuts."""
    published = dict(
        first_k_dense_replace=1, head_dim=72, hidden_size=2304,
        intermediate_size=9216, kv_lora_rank=512, model_max_length=1048576,
        moe_intermediate_size=1024, moe_layer_freq=1, num_attention_heads=32,
        num_expert_group=1, num_experts=256, num_experts_per_token=8,
        num_hidden_layers=27, num_key_value_heads=32,
        num_nextn_predict_layers=0, num_shared_experts=1,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-5,
        rope_theta=10000, routed_scaling_factor=2.446, topk_group=1,
        v_head_dim=128, vocab_size=163840)
    entry = {c["name"]: c for c in KIMI_MANIFEST["configs"]}[
        "kimi-linear-48b-a3b-train-ep16-l5"]
    cfg = KIMI_CONFIG
    changed = [k for k, v in published.items() if cfg[k] != v]
    assert sorted(changed + ["linear_attn_config"]) == \
        sorted(entry["reduced"]) == sorted(cfg["reduced"])
    la = cfg["linear_attn_config"]
    assert (la["head_dim"], la["num_heads"], la["short_conv_kernel_size"]) \
        == (128, 32, 4)
    assert la["kda_layers"] == [1, 2, 3, 5] and la["full_attn_layers"] == [4]
    assert {k: cfg["published"][k] for k in changed} == \
        {k: published[k] for k in changed}
    # the floors of a model_config cut: a whole period after the dense
    # layer, at least 8 experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]


def test_kimi_reducers_on_a_hand_trace():
    """kda_roofline from two kernel calls of known length; the MFU from a
    rate and a count of pairs; both leave the metric out with nothing to
    read."""
    from chipbench.reducers import kda_roofline, train_step_mfu_kimi
    parts = runmod.load_cell(KIMI_MANIFEST, KIMI_CELL)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    spec = {s["name"]: s for s in parts["layer_metrics"]}
    cell = dict(parts, peaks=peaks, spec=spec["kda_roofline.kimi"])
    kernel = ("%checkpoint_hvd_kda_fwd.7 = bf16[4,8192,4096]{2,1,0} "
              "custom-call(%a), custom_call_target=\"tpu_custom_call\"")
    user = "%fusion.1 = bf16[4,8192,4096]{2,1,0} fusion(%hvd_kda_fwd.7)"
    red = {"ops": [(0, kernel, 0.1, 0.02), (0, kernel, 0.2, 0.02),
                   (0, user, 0.3, 0.5)],
           "lo": 0.0, "hi": 1.0, "devices": [0]}
    _, byts = kcosts.kda_core_cost(KIMI_DIMS, 4, 8192)
    assert kda_roofline.reduce(red, {}, cell) == \
        pytest.approx(100 * 2 * (byts / 819e9) / 0.04)
    assert kda_roofline.reduce(dict(red, ops=[red["ops"][2]]), {}, cell) \
        is None
    cell = dict(parts, peaks=peaks, spec=spec["train_step_mfu.kimi"])
    counters = {"train_tokens_per_s_per_chip": 20000.0,
                "pairs_held_per_step": 65536.0}
    per_step = kcosts.train_flops_per_step(KIMI_DIMS, 4, 8192, 65536.0)
    assert train_step_mfu_kimi.reduce({}, counters, cell) == pytest.approx(
        100 * per_step * 20000.0 / 32768 / 197e12)
    assert train_step_mfu_kimi.reduce({}, {}, cell) is None
