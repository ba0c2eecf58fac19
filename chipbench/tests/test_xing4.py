"""Tests of what the Xing4.0-29B-A4B cell adds to the yardstick; on the CPU.

The configuration file against the published ``config.json``, the
parameter counts and costs by hand and the maps' FLOPs against XLA's own
count, the driver end to end at a tiny size (a sound run passes, the fp8
control does not, an altered token does not, a program without the model
exits at once), the named-cost readers on a hand-built trace, the
``hvd.hc`` shares on hand-built programs, and the manifest's new pieces
found by name.  Names here differ from those of the other files in this
directory: ``tests/test_chipbench.py`` loads them all into one namespace.
"""

from __future__ import annotations

import importlib
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import costs_xing4 as xcosts                    # noqa: E402
from chipbench import peaks as xpeaks                          # noqa: E402
from chipbench import run as runmod                            # noqa: E402
from chipbench import weights_xing4 as xweights                # noqa: E402

XING_BENCH = os.path.join(ROOT, "chipbench")
XING_MANIFEST = runmod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
XING_TINY = runmod.load_json(os.path.join(
    XING_BENCH, "testdata", "tiny_xing4", "BENCHMARK.json"))
XING_CELL = "xing4-serve-rag-backlog"
XING_CONFIG = runmod.load_json(os.path.join(
    XING_BENCH, "configs", "xing4.0-29b-a4b-serve-l7.json"))
XING_DIMS = xweights.dims_of(XING_CONFIG)
XING_METRICS = ("serve_step_mfu.xing4", "decode_tick_roofline.xing4",
                "mla_decode_roofline.xing4", "prefill_hc_share.xing4",
                "decode_hc_share.xing4")
XING_JOINED = ("batch_occupancy.backlog", "idle_under_prefill_host.backlog",
               "idle_under_decode_host.backlog",
               "idle_under_emit_deliver.backlog", "paged_table_fill.backlog",
               "prefill_program_ms_per_ktok.backlog",
               "decode_program_ms_p50.backlog", "decode_ahead_share.backlog",
               "serve_unnamed_share", "prefill_mixer_share",
               "prefill_mlp_share", "decode_mixer_share", "decode_mlp_share")

#: config.json of XingChen-AGI/Xing4.0-29B-A4B as the catalog copies it
XING_PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}


# -- the configuration --------------------------------------------------------

def test_xing_config_keeps_every_published_number():
    entry = {c["name"]: c for c in XING_MANIFEST["configs"]}[
        "xing4.0-29b-a4b-serve-l7"]
    assert entry["source"] == XING_CONFIG["source"] == \
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/" \
        "config.json"
    changed = [k for k, v in XING_PUBLISHED.items() if XING_CONFIG[k] != v]
    assert changed == entry["reduced"] == XING_CONFIG["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert XING_CONFIG["published"] == {"num_hidden_layers": 40,
                                        "num_nextn_predict_layers": 1}
    assert (XING_CONFIG["num_hidden_layers"],
            XING_CONFIG["num_nextn_predict_layers"]) == (7, 0)
    assert XING_CONFIG["torch_dtype"] == "bfloat16"
    assert XING_CONFIG["attention_path"] == "pallas-mla"
    assert XING_CONFIG["prefill_path"] == "flash"
    for what in ("streams", "maps", "hc_eps", "readout", "rope",
                 "softmax_scale", "weights", "mtp"):
        assert what in XING_CONFIG["assumed"]
    assert "first pipeline stage" in XING_CONFIG["deployment"]


def test_xing_weights_and_pool_fill_the_chip_as_the_file_says():
    eng = XING_CONFIG["engine"]
    assert (eng["block_size"], eng["max_active"]) == (32, 64)
    assert eng["prefix_cache"] is False and eng["prefill_token_budget"] == 1
    assert all(b % 512 == 0 for b in eng["prefill_buckets"])
    assert eng["prefill_buckets"][0] == 1024
    assert eng["prefill_buckets"][-1] == 8704
    c = xcosts.counts(XING_DIMS)
    weights = 2 * (c["held"] - xcosts.hc_weights(XING_DIMS)) + \
        4 * xcosts.hc_weights(XING_DIMS) + 2 * 7 * 64 * 3584 \
        + 2 * 5 * 3584                 # routers and gains in float32 too
    pool = eng["num_blocks"] * eng["block_size"] * 7 * 640 * 2
    said = XING_CONFIG["bytes_per_chip"]
    for number in ("4,920,924,095", "9.842 GB", f"{eng['num_blocks']:,}",
                   "8,960 B", "8,064 B"):
        assert number in said, number
    assert 0.78 < (weights + pool) / 17.18e9 < 0.86
    tr = runmod.load_cell(XING_MANIFEST, XING_CELL)["traffic"]
    assert max(eng["prefill_buckets"]) >= tr["prompt"]["hi"] + \
        tr["output"]["hi"] - 1


# -- counts and costs by hand -------------------------------------------------

def test_xing_parameter_counts_are_the_stated_ones():
    c = xcosts.counts(XING_DIMS)
    assert c["attention"] + 2 * 3584 == 28_418_304       # with both gains
    assert c["hc_layer"] == 688_182 and c["hc_head"] == 57_349
    assert c["dense_layer"] == 128_196_918
    assert c["expert_layer"] == 744_989_046
    assert c["experts"] == 64 * 11_010_048
    assert c["embed_and_head"] + 3584 + c["hc_head"] == 939_585_029
    assert c["held"] == 2 * 128_196_918 + 5 * 744_989_046 + 939_585_029 \
        == 4_920_924_095
    assert round(2 * c["held"] / 1e9, 3) == 9.842


def test_xing_costs_by_hand():
    d = XING_DIMS
    n, D, L = 4, 3584, 7
    assert xcosts.hc_token_flops(d) == 2 * n * D * (2 * L * 24 + n)
    assert xcosts.stream_bytes(d, 3) == 3 * 2 * n * D * (4 * L + 1)
    assert xcosts.hc_weights(d) == 7 * 688_182 + 57_349
    # a tick of two streams: GLM's count of this model, the maps and the
    # streams beside it
    from chipbench import costs_glm
    gf, gb = costs_glm.decode_tick_cost(d, [100, 200], 50)
    f, b = xcosts.decode_tick_cost(d, [100, 200], 50)
    assert f == gf + 2 * xcosts.hc_token_flops(d)
    hc = xcosts.hc_weights(d)
    assert xcosts.counts(d)["held"] == costs_glm.counts(d)["held"] + hc
    assert b == gb + 4 * hc + xcosts.stream_bytes(d, 2)     # maps in f32
    gf, gb = costs_glm.prefill_cost(d, 3000)
    f, b = xcosts.prefill_cost(d, 3000)
    assert f == gf + 3000 * xcosts.hc_token_flops(d)
    assert b == gb + 4 * hc + xcosts.stream_bytes(d, 3000)
    # the least tick at 64 rows of 3,500 tokens: about 13 ms (6.9 GB of
    # touched experts, 1.8 of other weights, 1.8 of live rows)
    pk = xpeaks.PEAKS["TPU v5 lite"]
    least = xcosts.least_seconds(*xcosts.decode_tick_cost(d, [3500] * 64),
                                 pk)
    assert 0.0125 < least < 0.0140
    # the latent kernel: 32 heads, the published 576 values a token
    kf, kb = xcosts.mla_decode_cost(d, [1000])
    assert (kf, kb) == (2 * 32 * (576 + 512) * 1000, 1152 * 1000)


def test_xing_map_flops_match_xla_cost_analysis():
    """XLA's own count of a sub-layer's maps and of the read-out, at the
    cell's widths on a few tokens, is the products' FLOPs that
    ``costs_xing4`` counts to within what the vector work beside them
    adds: the norm's 3 n D a token and sub-layer, the gates and the
    Sinkhorn rounds (9.6% at these widths)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import layers
    hc = layers.HyperConnections()
    T, n, D, m = 8, 4, 3584, 24
    f32 = jnp.float32
    X = jax.ShapeDtypeStruct((1, T, n, D), jnp.bfloat16)
    maps = jax.jit(lambda X, phi, b, a: layers.hc_maps(X, phi, b, a, hc))
    read = jax.jit(lambda X, phi, b, a: layers.hc_readout(X, phi, b, a, hc))
    flops = lambda fn, k: fn.lower(
        X, jax.ShapeDtypeStruct((n * D, k), f32),
        jax.ShapeDtypeStruct((k,), f32), jax.ShapeDtypeStruct((3,), f32)
    ).compile().cost_analysis()["flops"]
    counted = 2 * XING_DIMS["n_layers"] * flops(maps, m) + flops(read, n)
    want = T * xcosts.hc_token_flops(XING_DIMS)
    assert want <= counted <= 1.12 * want, (counted, want)


# -- the driver, at a size a test can hold ------------------------------------

@pytest.fixture(scope="module")
def xing_tiny_run():
    return runmod.execute(XING_TINY, "tiny-xing4", 2 ** 31 + 42, 3.0,
                          False, require_tpu=False, control=True)


def test_xing_tiny_sound_run_is_correct(xing_tiny_run):
    out = xing_tiny_run
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {"requests_failed", "answers_altered",
                                  "served_gap_mean", "clean_flip_margin_max"}
    import json
    json.dumps(out)                            # the result line is JSON


def test_xing_tiny_fp8_control_is_over_both_limits(xing_tiny_run):
    fp8 = xing_tiny_run["controls"]["fp8"]
    checks = xing_tiny_run["checks"]
    assert fp8["served_gap_mean"] > checks["served_gap_mean"]["limit"]
    assert fp8["picks_clean_flip_margin"] > \
        checks["clean_flip_margin_max"]["limit"]


def test_xing_tiny_altered_token_is_not_correct(monkeypatch):
    from horovod_tpu.serving import engine as eng
    real = eng.ServingEngine._emit

    def altered(self, req, token):
        return real(self, req, (token + 1) % self.cfg.vocab_size
                    if len(req.generated) == 2 else token)
    monkeypatch.setattr(eng.ServingEngine, "_emit", altered)
    bad = runmod.execute(XING_TINY, "tiny-xing4", 13, 3.0, False,
                         require_tpu=False)
    assert not bad["correct"], bad["checks"]


def test_xing_driver_exits_at_once_where_the_program_lacks_the_model():
    """The parent's program has no ``models.xing4``: the driver says so
    and exits non-zero before anything runs."""
    from chipbench.drivers import serve_named
    cfg = dict(XING_CONFIG, modules=dict(
        XING_CONFIG["modules"], program="horovod_tpu.models.no_such_model"))
    with pytest.raises(SystemExit, match="cannot state the configuration"):
        serve_named.load_modules(cfg, "bfloat16")
    cfg = dict(XING_CONFIG, modules=dict(XING_CONFIG["modules"],
                                         program_config="NoSuchConfig"))
    with pytest.raises(SystemExit, match="nothing ran"):
        serve_named.load_modules(cfg, "bfloat16")


# -- the named-cost readers, on a hand-built trace ----------------------------

def xing_hand_run():
    """Two traced turns in a window of 10 s: a first prefill of 3,000
    tokens with a tick of two streams (3,000 and 4,000 rows), then a tick
    of the same two; the decode program ran twice for 40 ms, the latent
    kernel 14 times for 2 ms in all; the ticks touched 30 and 36 of the
    320 (layer, expert) pairs."""
    kernel = "%hvd_mla_paged_decode.7 = bf16[64,32,512]{2,1,0} custom-call()"
    ops = [(0, kernel, 1.0 + 0.001 * i, 2e-3 / 14) for i in range(7)] + \
        [(0, kernel, 2.0 + 0.001 * i, 2e-3 / 14) for i in range(7)] + \
        [(0, "%fusion.1 = bf16[8] fusion(%hvd_mla_paged_decode.7)",
          1.04, 0.01)]
    tick = lambda touched: dict(experts_touched=touched, experts_held=320,
                                rows=2, kv_tokens=7000, hc_streams=4)
    red = {"ops": ops, "devices": [0], "lo": 0.0, "hi": 10.0,
           "window_s": 10.0, "busy_s": 0.1,
           "modules": [(0, "jit_hvd_serve_decode(7)", 1.0, 0.02),
                       (0, "jit_hvd_serve_decode(7)", 2.0, 0.02),
                       (0, "jit_hvd_serve_prefill(3)", 0.9, 0.03)],
           "spans": [("engine.step", 0.8, 0.4), ("engine.step", 1.9, 0.3)],
           "hvd_spans": [
               ("hvd.serve.prefill", 0.8, 0.1,
                dict(tokens=3000, resumed=0, hc_streams=4)),
               ("hvd.serve.decode", 1.0, 0.1, tick(30)),
               ("hvd.serve.decode", 2.0, 0.1, tick(36))]}
    counters = {"profile_span": (0.5, 9.0), "block_size": 32, "steps": [
        dict(t0=0.1, t1=0.2, prefill=[1024], resumed=[], decode=[]),
        dict(t0=0.8, t1=1.2, prefill=[3000], resumed=[],
             decode=[3000, 4000]),
        dict(t0=1.9, t1=2.2, prefill=[], resumed=[], decode=[3001, 4001])]}
    return red, counters


XING_PK = xpeaks.PEAKS["TPU v5 lite"]
XING_BY_HAND = {
    "serve_step_mfu.xing4": lambda d: 100.0 * (
        xcosts.prefill_cost(d, 3000)[0]
        + xcosts.decode_tick_cost(d, [3000, 4000])[0]
        + xcosts.decode_tick_cost(d, [3001, 4001])[0]) / 197e12 / 10.0,
    "decode_tick_roofline.xing4": lambda d: 100.0 * (
        xcosts.least_seconds(*xcosts.decode_tick_cost(
            d, [3000, 4000], 30), XING_PK)
        + xcosts.least_seconds(*xcosts.decode_tick_cost(
            d, [3001, 4001], 36), XING_PK)) / 0.04,
    "mla_decode_roofline.xing4": lambda d: 100.0 * 7 * (
        7000 + 7002) * 1152 / 819e9 / 2e-3,
}


@pytest.mark.parametrize("metric", sorted(XING_BY_HAND))
def test_xing_reader_on_the_hand_built_trace(metric):
    spec = runmod.load_json(os.path.join(XING_BENCH, "layer_metrics",
                                         metric + ".json"))
    red, counters = xing_hand_run()
    cell = {"spec": spec, "config": XING_CONFIG,
            "cell": {"name": XING_CELL}, "peaks": XING_PK, "chips": 1}
    reducer = importlib.import_module("chipbench.reducers." + spec["reducer"])
    got = reducer.reduce(red, counters, cell)
    assert got == pytest.approx(XING_BY_HAND[metric](XING_DIMS), rel=1e-9)
    assert 0.0 < got < 100.0
    # the parent's trace of this cell: no kernel, no program, no spans
    bare = dict(red, ops=[o for o in red["ops"] if "fusion" in o[1]],
                modules=[], hvd_spans=[])
    assert reducer.reduce(bare, dict(counters, steps=[]), cell) is None


# -- the stream region, on hand-built programs --------------------------------

def _xing_program(name, ops):
    """One program of ``(instruction, op_name under the program)``."""
    return {name: [{"id": 1, "name": "main", "instructions": [
        {"name": i, "opcode": "fusion", "id": k + 2,
         "op_name": f"jit({name.split('(')[0][4:]})/while/body/" + path}
        for k, (i, path) in enumerate(ops)]}]}


#: (instruction, region path, seconds) of a prefill and of a tick
XING_PREFILL = [("fusion.1", "hvd.block.mixer/hvd.hc/dot_general", 0.03),
                ("fusion.2", "hvd.block.mixer/dot_general", 0.07),
                ("fusion.3", "hvd.block.mlp/hvd.hc/mul", 0.02),
                ("fusion.4", "hvd.block.mlp/hvd.moe.experts/dot_general",
                 0.06),
                ("fusion.5", "hvd.head/hvd.hc/reduce_sum", 0.01),
                ("fusion.6", "hvd.head/dot_general", 0.01)]
XING_TICK = [("fusion.11", "hvd.block.mixer/hvd.hc/while/body/div", 0.01),
             ("fusion.12", "hvd.block.mixer/dot_general", 0.04),
             ("fusion.13", "hvd.block.mlp/hvd.hc/add", 0.01),
             ("fusion.14", "hvd.block.mlp/hvd.moe.experts/dot_general", 0.03),
             ("fusion.15", "hvd.head/hvd.hc/mul", 0.005),
             ("fusion.16", "hvd.head/dot_general", 0.005)]


def xing_region_red(tmp_path, monkeypatch):
    """The two programs as a traced run's reducer sees them, their
    compiled text in an xplane file where the region reader looks."""
    from chipbench import op_regions, trace
    spec = importlib.util.spec_from_file_location(
        "chipbench_xing_op_regions", os.path.join(XING_BENCH, "tests",
                                                  "test_op_regions.py"))
    helpers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(helpers)
    monkeypatch.setattr(op_regions, "OUT", str(tmp_path))
    programs = {**_xing_program("jit_hvd_serve_prefill(1)",
                                [(i, p) for i, p, _ in XING_PREFILL]),
                **_xing_program("jit_hvd_serve_decode(2)",
                                [(i, p) for i, p, _ in XING_TICK])}
    helpers._write_xplane(str(tmp_path / XING_CELL / "trace" / "plugins" /
                              "profile" / "1" / "x.xplane.pb"), programs)
    ops, t = [], 0.1
    for i, _, d in XING_PREFILL:
        ops.append((0, f"%{i} = bf16[8]{{0}} fusion(%p)", t, d))
        t += d
    t = 0.4
    for i, _, d in XING_TICK:
        ops.append((0, f"%{i} = bf16[8]{{0}} fusion(%p)", t, d))
        t += d
    ev = {"ops": ops, "spans": [("engine.step", 0.0, 1.0)],
          "modules": [(0, "jit_hvd_serve_prefill(1)", 0.1, 0.2),
                      (0, "jit_hvd_serve_decode(2)", 0.4, 0.1)]}
    return dict(trace.reduce_events(ev), **ev)


@pytest.mark.parametrize("metric,want", [
    ("prefill_hc_share.xing4", 100 * 0.06 / 0.2),
    ("decode_hc_share.xing4", 100 * 0.025 / 0.1),
    # the halves' shares keep counting the stream region inside them
    ("prefill_mixer_share", 100 * 0.10 / 0.2),
    ("decode_mlp_share", 100 * 0.04 / 0.1)])
def test_xing_stream_region_is_read_inside_each_half(tmp_path, monkeypatch,
                                                     metric, want):
    red = xing_region_red(tmp_path, monkeypatch)
    spec = runmod.load_json(os.path.join(XING_BENCH, "layer_metrics",
                                         metric + ".json"))
    reducer = importlib.import_module("chipbench.reducers." + spec["reducer"])
    got = reducer.reduce(red, {}, {"cell": {"name": XING_CELL}, "spec": spec})
    assert got == pytest.approx(want, rel=1e-9)


# -- the manifest's new pieces, found by name ---------------------------------

def xing_cell_found_whole(manifest, root=ROOT):
    """The Xing4.0 cell's pieces in ``manifest``, wherever in its lists
    they stand."""
    parts = runmod.load_cell(manifest, XING_CELL, root)
    assert parts["cell"]["chips"] == 1
    assert parts["cell"]["config"] == "xing4.0-29b-a4b-serve-l7"
    assert parts["cell"]["traffic"] == "serve-rag-backlog"
    tr = parts["traffic"]
    assert tr["driver"] == "serve_named" and tr["order_seed"] == 42
    assert tr["arrivals"] == {"kind": "backlog", "requests": 1200}
    assert tr["prompt"] == dict(dist="lognormal", median=3072, sigma=0.6,
                                lo=1024, hi=8192, levels=16)
    assert tr["output"] == dict(dist="lognormal", median=96, sigma=0.6,
                                lo=32, hi=256, levels=16)
    assert tr["shared_prefix"] == 0.0 and tr["warm_resumed"] is False
    assert tr["check_requests"] == 4
    assert set(tr["limits"]) == set(tr["limits_why"]) == {
        "served_gap_mean", "clean_flip_margin_max"}
    for key in ("weights", "reference", "costs"):
        importlib.import_module("chipbench." + parts["config"]["modules"][key])
    assert {m["name"] for m in parts["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    names = {s["name"] for s in parts["layer_metrics"]}
    assert set(XING_METRICS) | set(XING_JOINED) | {
        "compile_s", "compiles_in_window"} <= names
    for m in manifest["per_layer"]:
        if m["name"] in XING_METRICS:
            assert m["workloads"] == [XING_CELL]
            assert m["moves"] == "serve_tokens_per_s"
        if m["name"] in XING_JOINED:
            assert XING_CELL in m["workloads"]
    cells = manifest["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_xing_cell_is_found_whole():
    xing_cell_found_whole(XING_MANIFEST)


def test_xing_reference_imports_nothing_from_the_program():
    import ast
    for name in ("reference_xing4", "weights_xing4", "costs_xing4"):
        tree = ast.parse(open(os.path.join(XING_BENCH, name + ".py")).read())
        mods = [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)] + \
            [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
        assert not any(m.startswith("horovod_tpu") for m in mods), name
