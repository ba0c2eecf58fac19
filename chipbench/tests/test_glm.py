"""Tests of what the GLM-4.7-Flash cell adds to the yardstick; on the CPU.

The configuration file against the published ``config.json``, the costs
against the counts the issue states and by hand, the driver end to end at
a tiny size (a sound run passes, the fp8 control does not, an altered
token does not), the new reducers on a hand-built trace, and the
manifest's new pieces found by name.  Names here differ from those of
the other files in this directory: ``tests/test_chipbench.py`` loads
them all into one namespace.
"""

from __future__ import annotations

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import costs_glm as gcosts                      # noqa: E402
from chipbench import peaks as gpeaks                          # noqa: E402
from chipbench import run as runmod                            # noqa: E402
from chipbench import weights_glm as gweights                  # noqa: E402

GLM_BENCH = os.path.join(ROOT, "chipbench")
GLM_MANIFEST = runmod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
GLM_TINY = runmod.load_json(os.path.join(
    GLM_BENCH, "testdata", "tiny_glm", "BENCHMARK.json"))
GLM_CELL = "glm47flash-serve-code-backlog"
GLM_CONFIG = runmod.load_json(os.path.join(
    GLM_BENCH, "configs", "glm-4.7-flash-serve-l7.json"))
GLM_DIMS = gweights.dims_of(GLM_CONFIG)
GLM_METRICS = ("serve_step_mfu.glm", "decode_tick_roofline.glm",
               "mla_decode_roofline.glm", "mla_decode_kernel_share.glm",
               "moe_experts_touched.glm", "kv_pool_fill.glm")
GLM_JOINED = ("batch_occupancy.backlog", "idle_under_prefill_host.backlog",
              "idle_under_decode_host.backlog",
              "idle_under_emit_deliver.backlog", "paged_table_fill.backlog",
              "prefill_program_ms_per_ktok.backlog",
              "decode_program_ms_p50.backlog")

#: config.json of zai-org/GLM-4.7-Flash as the catalog copies it
GLM_PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=2048,
    intermediate_size=10240, max_position_embeddings=202752,
    model_type="glm4_moe_lite", moe_intermediate_size=1536,
    topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
    n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
    routed_scaling_factor=1.8, num_experts_per_tok=4,
    first_k_dense_replace=1, num_hidden_layers=47, num_key_value_heads=20,
    num_nextn_predict_layers=1, partial_rotary_factor=1, rms_norm_eps=1e-05,
    rope_scaling=None, rope_theta=1000000, tie_word_embeddings=False,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, vocab_size=154880)


# -- the configuration ------------------------------------------------------------

def test_glm_config_keeps_every_published_number():
    entry = {c["name"]: c for c in GLM_MANIFEST["configs"]}[
        "glm-4.7-flash-serve-l7"]
    assert entry["source"] == GLM_CONFIG["source"] == \
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    changed = [k for k, v in GLM_PUBLISHED.items() if GLM_CONFIG[k] != v]
    assert changed == entry["reduced"] == GLM_CONFIG["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert GLM_CONFIG["published"] == {k: GLM_PUBLISHED[k] for k in changed}
    assert (GLM_CONFIG["num_hidden_layers"],
            GLM_CONFIG["num_nextn_predict_layers"]) == (7, 0)
    assert GLM_CONFIG["torch_dtype"] == "bfloat16"
    assert GLM_CONFIG["attention_path"] == "pallas-mla"
    assert GLM_CONFIG["prefill_path"] == "flash"
    for what in ("rope", "softmax_scale", "router", "mtp", "weights"):
        assert what in GLM_CONFIG["assumed"]
    assert "first pipeline stage" in GLM_CONFIG["deployment"]
    # no width is cut: none of the reduced keys is one
    assert not [k for k in entry["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))]


def test_glm_pool_and_weights_fill_the_chip_as_the_file_says():
    eng = GLM_CONFIG["engine"]
    assert (eng["block_size"], eng["num_blocks"], eng["max_active"]) == \
        (32, 18432, 64)
    assert eng["prefill_buckets"] == [2048, 3072, 4096, 5120, 6144, 8192,
                                      10240, 12288, 13312]
    assert all(b % 512 == 0 for b in eng["prefill_buckets"])
    assert gcosts.cache_bytes_per_token(GLM_DIMS) == 7 * 576 * 2 == 8064
    padded = 7 * 640 * 2                       # the pool's row of 640
    pool = eng["num_blocks"] * eng["block_size"] * padded
    weights = 2 * gcosts.counts(GLM_DIMS)["held"]
    said = GLM_CONFIG["bytes_per_chip"]
    assert "4,530,936,960" in said and "8,064 B" in said and "8,960 B" in said
    assert round(weights / 1e9, 3) == 9.062 and round(pool / 1e9, 3) == 5.285
    assert 0.80 < (pool + weights) / 17.18e9 < 0.86
    assert (eng["num_blocks"] - 1) * eng["block_size"] == 589_792
    assert max(eng["prefill_buckets"]) >= 12288 + 1024 - 1


# -- costs, against the issue's counts and by hand ---------------------------------

def test_glm_parameter_counts_are_the_stated_ones():
    c = gcosts.counts(GLM_DIMS)
    assert c["attention"] == 21_759_232
    assert c["dense_layer"] == 84_677_888
    assert c["expert_layer"] == 635_311_424
    assert c["experts"] == 603_979_776
    assert c["embed_and_head"] == 634_388_480
    assert c["held"] == 84_677_888 + 6 * 635_311_424 + 634_388_480 + 2048 \
        == 4_530_936_960
    whole = dict(GLM_DIMS, n_layers=47)
    assert round(gcosts.counts(whole)["held"] / 1e9, 2) == 29.94
    assert gcosts.expert_weights(GLM_DIMS) * 2 == 18_874_368


def test_glm_costs_count_four_experts_and_the_published_cache():
    d = GLM_DIMS
    attn = 21_759_232 - 768 - 512                      # gains left out
    assert gcosts.attention_weights(d) == attn
    per_token = 7 * attn + 3 * 2048 * 10240 + 6 * (
        2048 * 64 + 5 * 3 * 2048 * 1536)
    assert gcosts.token_weights(d) == per_token
    # a prompt of 100: expanded pairs in 7 layers, the head once
    flops, bytes_ = gcosts.prefill_cost(d, 100)
    assert flops == 2 * per_token * 100 + 7 * 2 * 20 * 512 * 5050 \
        + 2 * 2048 * 154880
    assert bytes_ == 2 * (4_530_936_960 - 2048 * 154880 + 100 * 2048) \
        + 100 * 8064
    # a tick of two streams, 200 and 300 rows, 5 (layer, expert) touched
    flops, bytes_ = gcosts.decode_tick_cost(d, [200, 300], 5)
    assert gcosts.pair_flops(d, True) == 2 * 20 * (576 + 512) == 43_520
    assert flops == 2 * (per_token + 2048 * 154880) * 2 + 7 * 43_520 * 500
    fixed = 4_530_936_960 - 2048 * 154880 - 6 * 603_979_776
    assert bytes_ == 2 * (fixed + 2 * 2048 + 5 * 9_437_184) + 7 * 1152 * 500
    # untold, a tick can touch min(streams x 4, 64) experts a layer
    assert gcosts.decode_tick_cost(d, [200, 300])[1] == \
        gcosts.decode_tick_cost(d, [200, 300], 6 * 8)[1]
    pk = gpeaks.PEAKS["TPU v5 lite"]
    kf, kb = gcosts.mla_decode_cost(d, [200, 300])
    assert (kf, kb) == (43_520 * 500, 1152 * 500)
    assert gcosts.least_seconds(kf, kb, pk) == kb / pk["hbm_bytes_per_s"]


# -- the driver, at a size a test can hold ------------------------------------------

@pytest.fixture(scope="module")
def glm_tiny_run():
    return runmod.execute(GLM_TINY, "tiny-glm", 2 ** 31 + 7, 3.0, False,
                          require_tpu=False, control=True)


def test_glm_tiny_sound_run_is_correct(glm_tiny_run):
    out = glm_tiny_run
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {
        "requests_failed", "answers_altered", "served_gap_max",
        "clean_flip_margin_max"}
    import json
    json.dumps(out)                            # the result line is JSON


def test_glm_tiny_fp8_control_is_over_both_limits(glm_tiny_run):
    fp8 = glm_tiny_run["controls"]["fp8"]
    checks = glm_tiny_run["checks"]
    assert fp8["served_gap_max"] > 2 * checks["served_gap_max"]["limit"]
    assert fp8["picks_clean_flip_margin"] > \
        2 * checks["clean_flip_margin_max"]["limit"]
    assert fp8["picks_agree_share"] < 1.0


def test_glm_tiny_altered_token_is_not_correct(monkeypatch):
    from horovod_tpu.serving import engine as eng
    real = eng.ServingEngine._emit

    def altered(self, req, token):
        return real(self, req, (token + 1) % self.cfg.vocab_size
                    if len(req.generated) == 2 else token)
    monkeypatch.setattr(eng.ServingEngine, "_emit", altered)
    bad = runmod.execute(GLM_TINY, "tiny-glm", 11, 3.0, False,
                         require_tpu=False)
    assert not bad["correct"], bad["checks"]


def test_glm_driver_exits_cleanly_where_the_program_lacks_the_model():
    from chipbench.drivers import serve_named
    cfg = dict(GLM_CONFIG, modules=dict(
        GLM_CONFIG["modules"], program="horovod_tpu.models.no_such_model"))
    with pytest.raises(SystemExit, match="nothing ran"):
        serve_named.load_modules(cfg, "bfloat16")


def test_glm_driver_warms_every_prompt_shape_and_the_resumed_bucket():
    from chipbench import loadgen
    from chipbench.drivers import serve
    parts = runmod.load_cell(GLM_MANIFEST, GLM_CELL)
    eng, tr = parts["config"]["engine"], parts["traffic"]
    lens = serve._warm_lengths(tr, eng["block_size"])
    assert set(loadgen.levels(tr["prompt"])) <= set(lens)
    bucket = lambda n: next(b for b in eng["prefill_buckets"] if n <= b)
    assert {bucket(n) for n in lens} == set(eng["prefill_buckets"][:-1])
    assert tr["warm_resumed"] is False
    assert bucket(12288 + 1024 - 1) == 13312     # warmed by its own prompt
    # every table width a context of the mix can reach
    pow2 = lambda n: 1 << (n - 1).bit_length()
    widths = {pow2(-(-(n + 1) // 32)) for n in range(2048, 12288 + 1024)}
    assert widths == {pow2(-(-(p + 1) // 32)) for p in lens} == \
        {128, 256, 512}


# -- the new reducers, on a hand-built trace -------------------------------------

def glm_hand_run():
    """Two traced turns in a window of 10 s: a first prefill of 4,000
    tokens with a tick of two streams (5,000 and 7,000 rows), then a
    resumed prefill with the same tick; the decode program ran twice for
    40 ms, the latent kernel 14 times for 2 ms in all; the ticks touched
    100 and 140 of 384 (layer, expert) pairs; the pool stood at 9,000 and
    10,000 of 18,431 blocks."""
    kernel = "%hvd_mla_paged_decode.11 = bf16[64,32,512]{2,1,0} custom-call()"
    ops = [(0, kernel, 1.0 + 0.001 * i, 2e-3 / 14) for i in range(7)] + \
        [(0, kernel, 2.0 + 0.001 * i, 2e-3 / 14) for i in range(7)] + \
        [(0, "%fusion.1 = bf16[8] fusion(%hvd_mla_paged_decode.11)",
          1.04, 0.01)]
    tick = lambda held, touched: dict(
        blocks_held=held, blocks_usable=18431, experts_touched=touched,
        experts_held=384, moe_pairs=1536, kv_tokens=12000)
    red = {"ops": ops, "devices": [0], "lo": 0.0, "hi": 10.0,
           "window_s": 10.0, "busy_s": 0.1,
           "modules": [(0, "jit_hvd_serve_decode(7)", 1.0, 0.04),
                       (0, "jit_hvd_serve_decode(7)", 2.0, 0.04),
                       (0, "jit_hvd_serve_prefill(3)", 0.9, 0.03)],
           "spans": [("engine.step", 0.8, 0.4), ("engine.step", 1.9, 0.3)],
           "hvd_spans": [
               ("hvd.serve.prefill", 0.8, 0.1, dict(tokens=4000, resumed=0)),
               ("hvd.serve.decode", 1.0, 0.1, tick(9000, 100)),
               ("hvd.serve.prefill", 1.9, 0.1,
                dict(tokens=5000, resumed=5000)),
               ("hvd.serve.decode", 2.0, 0.1, tick(10000, 140))]}
    counters = {"profile_span": (0.5, 9.0), "block_size": 32, "steps": [
        dict(t0=0.1, t1=0.2, prefill=[2048], resumed=[], decode=[]),
        dict(t0=0.8, t1=1.2, prefill=[4000], resumed=[],
             decode=[5000, 7000]),
        dict(t0=1.9, t1=2.2, prefill=[], resumed=[5000],
             decode=[5000, 7000])]}
    return red, counters


GLM_BY_HAND = {
    "serve_step_mfu.glm": lambda d: 100.0 * (
        gcosts.prefill_cost(d, 4000)[0]
        + 2 * gcosts.decode_tick_cost(d, [5000, 7000])[0]) / 197e12 / 10.0,
    "decode_tick_roofline.glm": lambda d: 100.0 * (
        gcosts.decode_tick_cost(d, [5000, 7000], 100)[1]
        + gcosts.decode_tick_cost(d, [5000, 7000], 140)[1]) / 819e9 / 0.08,
    # 12,000 live rows x 7 layers x 1,152 B a tick, two ticks, over 2 ms
    "mla_decode_roofline.glm": lambda d: 100.0 * 2 * 12000 * 7 * 1152
    / 819e9 / 2e-3,
    "mla_decode_kernel_share.glm": lambda d: 100.0 * 2e-3 / 0.1,
    "moe_experts_touched.glm": lambda d: 100.0 * 240 / 768,
    "kv_pool_fill.glm": lambda d: 100.0 * 19000 / 36862,
}


@pytest.mark.parametrize("metric", GLM_METRICS)
def test_glm_reducer_on_the_hand_built_trace(metric):
    spec = runmod.load_json(os.path.join(GLM_BENCH, "layer_metrics",
                                         metric + ".json"))
    red, counters = glm_hand_run()
    cell = {"spec": spec, "config": GLM_CONFIG, "cell": {"name": GLM_CELL},
            "peaks": gpeaks.PEAKS["TPU v5 lite"], "chips": 1}
    reducer = importlib.import_module("chipbench.reducers." + spec["reducer"])
    got = reducer.reduce(red, counters, cell)
    assert got == pytest.approx(GLM_BY_HAND[metric](GLM_DIMS), rel=1e-9)
    assert 0.0 < got < 100.0
    # a program without the kernel, the program names or the attributes
    # (the parent's): nothing, and no raise
    red, _ = glm_hand_run()
    bare = dict(red, ops=[o for o in red["ops"] if "fusion" in o[1]],
                modules=red["modules"][2:], hvd_spans=[
                    (n, t, d, {"tokens": 1}) for n, t, d, _ in
                    red["hvd_spans"]])
    quiet = reducer.reduce(bare, dict(counters, steps=[]), cell)
    assert quiet is None
    # and on a configuration that names no modules (another cell's)
    other = dict(cell, config={k: v for k, v in GLM_CONFIG.items()
                               if k != "modules"})
    if spec["reducer"] == "named_costs":
        assert reducer.reduce(*glm_hand_run(), other) is None


def test_glm_decode_tick_roofline_with_spans_the_trace_cut():
    """One span fewer than ticks: the mean of the spans' counts a tick."""
    spec = runmod.load_json(os.path.join(
        GLM_BENCH, "layer_metrics", "decode_tick_roofline.glm.json"))
    red, counters = glm_hand_run()
    red["hvd_spans"] = red["hvd_spans"][:3]
    cell = {"spec": spec, "config": GLM_CONFIG, "cell": {"name": GLM_CELL},
            "peaks": gpeaks.PEAKS["TPU v5 lite"], "chips": 1}
    from chipbench.reducers import named_costs
    got = named_costs.reduce(red, counters, cell)
    assert got == pytest.approx(100.0 * 2 * gcosts.decode_tick_cost(
        GLM_DIMS, [5000, 7000], 100)[1] / 819e9 / 0.08, rel=1e-9)


# -- the manifest's new pieces, found by name --------------------------------------

def test_glm_cell_is_found_whole():
    parts = runmod.load_cell(GLM_MANIFEST, GLM_CELL)
    assert parts["cell"]["chips"] == 1
    assert parts["cell"]["config"] == "glm-4.7-flash-serve-l7"
    assert parts["cell"]["traffic"] == "serve-code-backlog"
    tr = parts["traffic"]
    assert tr["driver"] == "serve_named" and tr["order_seed"] == 35
    assert tr["arrivals"] == {"kind": "backlog", "requests": 600}
    assert tr["prompt"] == dict(dist="lognormal", median=6144, sigma=0.6,
                                lo=2048, hi=12288, levels=16)
    assert tr["output"] == dict(dist="lognormal", median=384, sigma=0.6,
                                lo=128, hi=1024, levels=16)
    assert tr["profile"] == {"start_s": 8.0, "seconds": 3.0}
    assert tr["check_requests"] == 4 and tr["block"] == 16
    assert tr["shared_prefix"] == 0.0
    importlib.import_module("chipbench.drivers.serve_named")
    for name in parts["config"]["modules"].values():
        if isinstance(name, str) and not name.startswith("horovod_tpu") \
                and name != "GlmMoeLiteConfig":
            importlib.import_module("chipbench." + name)
    assert {m["name"] for m in parts["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    names = {s["name"] for s in parts["layer_metrics"]}
    assert set(GLM_METRICS) | set(GLM_JOINED) | {
        "compile_s", "compiles_in_window"} == names
    for m in GLM_MANIFEST["per_layer"]:
        if m["name"] in GLM_METRICS:
            assert m["workloads"] == [GLM_CELL]
            assert m["moves"] == "serve_tokens_per_s"
        if m["name"] in GLM_JOINED:
            assert m["workloads"][-1] == GLM_CELL
    # the new entries stand at the end of their lists
    assert GLM_MANIFEST["workloads"][-1]["name"] == GLM_CELL
    assert GLM_MANIFEST["configs"][-1]["name"] == "glm-4.7-flash-serve-l7"
    assert [m["name"] for m in GLM_MANIFEST["per_layer"][-6:]] == \
        list(GLM_METRICS)
    assert sum(w["chips"] == 4 for w in GLM_MANIFEST["workloads"]) == 1


def test_glm_reference_imports_nothing_from_the_program():
    import ast
    for name in ("reference_glm", "weights_glm", "costs_glm"):
        tree = ast.parse(open(os.path.join(GLM_BENCH, name + ".py")).read())
        mods = [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)] + \
            [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
        assert not any(m.startswith("horovod_tpu") for m in mods), name
    src = open(os.path.join(GLM_BENCH, "reference_glm.py")).read()
    assert "mla_absorb" not in src and "w_uk" not in src    # expanded only
    src = open(os.path.join(GLM_BENCH, "reference.py")).read()
    assert 'precision="highest"' in src                     # its _mm
