"""Tests of what the Falcon-H1 cell adds to the yardstick; on the CPU.

The configuration file against the published ``config.json``, the costs
against the parameter counts the model's equations give and by hand, the
driver end to end at a tiny size (a sound run passes, the fp8 control
does not, an altered token does not), the new reducer on a hand-built trace, and the
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

from chipbench import costs_falcon_h1 as fcosts                # noqa: E402
from chipbench import peaks as fpeaks                          # noqa: E402
from chipbench import run as runmod                            # noqa: E402
from chipbench import weights_falcon_h1 as fweights            # noqa: E402

FALCON_BENCH = os.path.join(ROOT, "chipbench")
FALCON_MANIFEST = runmod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
FALCON_TINY = runmod.load_json(os.path.join(
    FALCON_BENCH, "testdata", "tiny_falcon", "BENCHMARK.json"))
FALCON_CELL = "falconh1-serve-chat-backlog"
FALCON_CONFIG = runmod.load_json(os.path.join(
    FALCON_BENCH, "configs", "falcon-h1-34b-serve-l6.json"))
FALCON_DIMS = fweights.dims_of(FALCON_CONFIG)
FALCON_METRICS = ("ssd_decode_roofline.falcon", "decode_tick_roofline.falcon",
                  "serve_step_mfu.falcon", "decode_ssm_share.falcon",
                  "prefill_ssm_share.falcon")
FALCON_JOINED = ("batch_occupancy.backlog", "idle_under_prefill_host.backlog",
                 "idle_under_decode_host.backlog",
                 "idle_under_emit_deliver.backlog", "paged_table_fill.backlog",
                 "prefill_program_ms_per_ktok.backlog",
                 "decode_program_ms_p50.backlog",
                 "paged_decode_kernel_share.backlog", "serve_unnamed_share",
                 "prefill_mixer_share", "prefill_mlp_share",
                 "decode_mixer_share", "decode_mlp_share")

#: config.json of tiiuae/Falcon-H1-34B-Instruct as the catalog copies it
FALCON_PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


# -- the configuration ------------------------------------------------------------

def test_falcon_config_keeps_every_published_number():
    entry = {c["name"]: c for c in FALCON_MANIFEST["configs"]}[
        "falcon-h1-34b-serve-l6"]
    assert entry["source"] == FALCON_CONFIG["source"] == \
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/" \
        "config.json"
    changed = [k for k, v in FALCON_PUBLISHED.items()
               if FALCON_CONFIG[k] != v]
    assert changed == entry["reduced"] == FALCON_CONFIG["reduced"] == [
        "num_hidden_layers"]
    assert FALCON_CONFIG["published"] == {"num_hidden_layers": 72}
    assert FALCON_CONFIG["num_hidden_layers"] == 6
    assert FALCON_CONFIG["torch_dtype"] == "bfloat16"
    assert FALCON_CONFIG["attention_path"] == "pallas"
    assert FALCON_CONFIG["prefill_path"] == "flash"
    for what in ("multipliers", "segments", "group_norm", "ssm", "rope",
                 "state_dtype", "weights"):
        assert what in FALCON_CONFIG["assumed"]
    assert "first pipeline stage" in FALCON_CONFIG["deployment"]


def test_falcon_weights_slots_and_pool_fill_the_chip_as_the_file_says():
    eng = FALCON_CONFIG["engine"]
    assert (eng["block_size"], eng["max_active"]) == (16, 128)
    assert eng["prefill_buckets"] == [512, 1024, 1536, 2048, 3072, 4096]
    assert all(b % 512 == 0 for b in eng["prefill_buckets"])
    assert eng["prefix_cache"] is False
    weights = 2 * fcosts.counts(FALCON_DIMS)["held"]
    slots = eng["max_active"] * fcosts.state_bytes_per_row(FALCON_DIMS)
    pool = eng["num_blocks"] * eng["block_size"] * \
        fcosts.kv_bytes_per_token(FALCON_DIMS)
    assert fcosts.kv_bytes_per_token(FALCON_DIMS) == 12_288
    assert fcosts.state_bytes_per_row(FALCON_DIMS) == \
        6 * (2_097_152 + 30_720)
    assert round(weights / 1e9, 3) == 10.509
    assert round(slots / 1e9, 3) == 1.634
    said = FALCON_CONFIG["bytes_per_chip"]
    for number in ("5,254,594,112", "10.509 GB", "1.634 GB", "12,288 B",
                   f"{eng['num_blocks']:,}"):
        assert number in said, number
    assert 0.80 < (weights + slots + pool) / 17.18e9 < 0.90
    # a preempted request comes back as prompt plus output
    tr = runmod.load_cell(FALCON_MANIFEST, FALCON_CELL)["traffic"]
    assert max(eng["prefill_buckets"]) >= tr["prompt"]["hi"] + \
        tr["output"]["hi"] - 1


# -- costs, against the equations' counts and by hand ------------------------------

def test_falcon_parameter_counts_are_the_stated_ones():
    c = fcosts.counts(FALCON_DIMS)
    assert (c["attention"], c["ssm"], c["mlp"], c["norms"]) == (
        31_457_280, 68_351_072, 330_301_440, 10_240)
    assert c["layer"] == 430_120_032
    assert c["embed_and_head"] == 2_673_868_800
    assert c["held"] == 6 * 430_120_032 + 2_673_868_800 + 5120 \
        == 5_254_594_112
    assert fweights.in_dim(FALCON_DIMS) == 9248
    assert fweights.conv_dim(FALCON_DIMS) == 5120


def test_falcon_costs_by_hand():
    d = FALCON_DIMS
    lw = 430_120_032 - 10_240 - 4 * 5120 - 5120 - 3 * 32 - 4096
    assert fcosts.layer_weights(d) == lw          # the matrices a token uses
    assert fcosts.ssm_token_flops(d) == 5 * 32 * 128 * 256 + 2 * 4 * 5120
    pk = fpeaks.PEAKS["TPU v5 lite"]
    # a tick of two streams reading 300 tokens and both rows' slots
    sb = 2 * 2 * fcosts.state_bytes_per_row(d)
    flops, bytes_ = fcosts.tick_cost(d, 2, 300, sb)
    head = 5120 * 261120
    assert flops == 2 * (6 * lw + head) * 2 + 6 * 4 * 20 * 128 * 300 + \
        6 * fcosts.ssm_token_flops(d) * 2
    assert bytes_ == 2 * (5_254_594_112 - head) + 2 * 2 * 5120 + \
        300 * 12_288 + sb
    assert fcosts.decode_tick_cost(d, [100, 200]) == (flops, bytes_)
    # the least tick at 128 rows and 950-token contexts: 15.4 ms
    least = fcosts.least_seconds(*fcosts.decode_tick_cost(d, [950] * 128), pk)
    assert 0.0150 < least < 0.0158
    # the kernel: the state both ways and its float32 inputs and output
    kf, kb = fcosts.ssd_decode_cost(d, 128)
    io = 4 * (2 * 32 * 128 + 32 + 2 * 2 * 256)
    assert kb == 6 * 128 * (2 * 2_097_152 + io)
    assert fcosts.least_seconds(kf, kb, pk) == kb / 819e9
    # a prompt: causal pairs, the recurrence a token, the head once
    flops, bytes_ = fcosts.prefill_cost(d, 100)
    assert flops == 2 * 6 * lw * 100 + 6 * 4 * 20 * 128 * 5050 + \
        6 * fcosts.ssm_token_flops(d) * 100 + 2 * head
    assert bytes_ == 2 * (5_254_594_112 - head) + 2 * 100 * 5120 + \
        100 * 12_288 + fcosts.state_bytes_per_row(d)


# -- the driver, at a size a test can hold ------------------------------------------

@pytest.fixture(scope="module")
def falcon_tiny_run():
    return runmod.execute(FALCON_TINY, "tiny-falcon", 2 ** 31 + 40, 3.0,
                          False, require_tpu=False, control=True)


def test_falcon_tiny_sound_run_is_correct(falcon_tiny_run):
    out = falcon_tiny_run
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {"requests_failed", "answers_altered",
                                  "served_gap_max", "served_gap_mean"}
    import json
    json.dumps(out)                            # the result line is JSON


def test_falcon_tiny_fp8_control_is_over_both_limits(falcon_tiny_run):
    fp8 = falcon_tiny_run["controls"]["fp8"]
    checks = falcon_tiny_run["checks"]
    assert fp8["served_gap_max"] > 2 * checks["served_gap_max"]["limit"]
    assert fp8["served_gap_mean"] > checks["served_gap_mean"]["limit"]


def test_falcon_tiny_altered_token_is_not_correct(monkeypatch):
    from horovod_tpu.serving import engine as eng
    real = eng.ServingEngine._emit

    def altered(self, req, token):
        return real(self, req, (token + 1) % self.cfg.vocab_size
                    if len(req.generated) == 2 else token)
    monkeypatch.setattr(eng.ServingEngine, "_emit", altered)
    bad = runmod.execute(FALCON_TINY, "tiny-falcon", 11, 3.0, False,
                         require_tpu=False)
    assert not bad["correct"], bad["checks"]


def test_falcon_driver_exits_cleanly_where_the_program_lacks_the_model():
    from chipbench.drivers import serve_named
    cfg = dict(FALCON_CONFIG, modules=dict(
        FALCON_CONFIG["modules"], program="horovod_tpu.models.no_such_model"))
    with pytest.raises(SystemExit, match="nothing ran"):
        serve_named.load_modules(cfg, "bfloat16")


def test_falcon_driver_warms_every_bucket_and_table_width():
    from chipbench import loadgen
    from chipbench.drivers import serve
    parts = runmod.load_cell(FALCON_MANIFEST, FALCON_CELL)
    eng, tr = parts["config"]["engine"], parts["traffic"]
    lens = serve._warm_lengths(tr, eng["block_size"])
    top = max(loadgen.levels(tr["prompt"])) + \
        max(loadgen.levels(tr["output"])) - 1
    assert tr["warm_resumed"] is False
    bucket = lambda n: next(b for b in eng["prefill_buckets"] if n <= b)
    assert {bucket(n) for n in lens + [top]} == set(eng["prefill_buckets"])
    pow2 = lambda n: 1 << (n - 1).bit_length()
    blocks = lambda n: -(-(n + 1) // eng["block_size"])
    reach = {pow2(blocks(n)) for n in range(min(lens), top + 1)}
    assert reach <= {pow2(blocks(p)) for p in lens}


# -- the new reducer, on a hand-built trace ---------------------------------------

def falcon_hand_run():
    """Two traced turns in a window of 10 s: a first prefill of 500
    tokens with a tick of two rows, then a tick of the same two; the
    decode program ran twice for 40 ms, the SSM kernel 12 times for 4 ms
    in all; the ticks read 1,000 and 1,002 cached tokens."""
    kernel = "%hvd_ssd_decode.3 = (f32[128,32,1,128]{3,2,1,0}) custom-call()"
    ops = [(0, kernel, 1.0 + 0.001 * i, 2e-3 / 6) for i in range(6)] + \
        [(0, kernel, 2.0 + 0.001 * i, 2e-3 / 6) for i in range(6)] + \
        [(0, "%fusion.1 = bf16[8] fusion(%hvd_ssd_decode.3)", 1.04, 0.01)]
    row = fcosts.state_bytes_per_row(FALCON_DIMS)
    tick = lambda kv: dict(rows=2, kv_tokens=kv, state_rows=2,
                           state_bytes=2 * 2 * row)
    red = {"ops": ops, "devices": [0], "lo": 0.0, "hi": 10.0,
           "window_s": 10.0, "busy_s": 0.1,
           "modules": [(0, "jit_hvd_serve_decode(7)", 1.0, 0.02),
                       (0, "jit_hvd_serve_decode(7)", 2.0, 0.02),
                       (0, "jit_hvd_serve_prefill(3)", 0.9, 0.03)],
           "spans": [("engine.step", 0.8, 0.4), ("engine.step", 1.9, 0.3)],
           "hvd_spans": [
               ("hvd.serve.prefill", 0.8, 0.1,
                dict(tokens=500, resumed=0, state_bytes=row)),
               ("hvd.serve.decode", 1.0, 0.1, tick(1000)),
               ("hvd.serve.decode", 2.0, 0.1, tick(1002))]}
    counters = {"profile_span": (0.5, 9.0), "block_size": 16, "steps": [
        dict(t0=0.1, t1=0.2, prefill=[300], resumed=[], decode=[]),
        dict(t0=0.8, t1=1.2, prefill=[500], resumed=[], decode=[499, 501]),
        dict(t0=1.9, t1=2.2, prefill=[], resumed=[], decode=[500, 502])]}
    return red, counters


FALCON_BY_HAND = {
    "ssd_decode_roofline.falcon": lambda d: 100.0 * 2 * (
        fcosts.ssd_decode_cost(d, 2)[1] / 819e9) / 4e-3,
    "decode_tick_roofline.falcon": lambda d: 100.0 * sum(
        fcosts.least_seconds(*fcosts.tick_cost(
            d, 2, kv, 4 * fcosts.state_bytes_per_row(d)),
            fpeaks.PEAKS["TPU v5 lite"]) for kv in (1000, 1002)) / 0.04,
    "serve_step_mfu.falcon": lambda d: 100.0 * (
        fcosts.prefill_cost(d, 500)[0]
        + fcosts.decode_tick_cost(d, [499, 501])[0]
        + fcosts.decode_tick_cost(d, [500, 502])[0]) / 197e12 / 10.0,
}


@pytest.mark.parametrize("metric", sorted(FALCON_BY_HAND))
def test_falcon_reducer_on_the_hand_built_trace(metric):
    spec = runmod.load_json(os.path.join(FALCON_BENCH, "layer_metrics",
                                         metric + ".json"))
    red, counters = falcon_hand_run()
    cell = {"spec": spec, "config": FALCON_CONFIG,
            "cell": {"name": FALCON_CELL},
            "peaks": fpeaks.PEAKS["TPU v5 lite"], "chips": 1}
    reducer = importlib.import_module("chipbench.reducers." + spec["reducer"])
    got = reducer.reduce(red, counters, cell)
    assert got == pytest.approx(FALCON_BY_HAND[metric](FALCON_DIMS),
                                rel=1e-9)
    assert 0.0 < got < 100.0
    # a program without the kernel, the program names or the attributes
    # (the parent's): nothing, and no raise
    bare = dict(red, ops=[o for o in red["ops"] if "fusion" in o[1]],
                modules=red["modules"][2:], hvd_spans=[
                    (n, t, dd, {"tokens": 1}) for n, t, dd, _ in
                    red["hvd_spans"]])
    assert reducer.reduce(bare, dict(counters, steps=[]), cell) is None
    # and on a configuration that names no modules (another cell's)
    other = dict(cell, config={k: v for k, v in FALCON_CONFIG.items()
                               if k != "modules"})
    assert reducer.reduce(*falcon_hand_run(), other) is None


def test_falcon_region_shares_read_the_ssm_region():
    for name, program in (("decode_ssm_share.falcon", "hvd_serve_decode"),
                          ("prefill_ssm_share.falcon", "hvd_serve_prefill")):
        spec = runmod.load_json(os.path.join(FALCON_BENCH, "layer_metrics",
                                             name + ".json"))
        assert spec["reducer"] == "region_share"
        assert spec["args"] == {"regions": ["hvd.ssm"],
                                "programs": [program]}


# -- the manifest's new pieces, found by name --------------------------------------

def falcon_cell_found_whole(manifest, root=ROOT):
    """The Falcon-H1 cell's pieces in ``manifest``, wherever in its lists
    they stand."""
    parts = runmod.load_cell(manifest, FALCON_CELL, root)
    assert parts["cell"]["chips"] == 1
    assert parts["cell"]["config"] == "falcon-h1-34b-serve-l6"
    assert parts["cell"]["traffic"] == "serve-chat-backlog"
    tr = parts["traffic"]
    assert tr["driver"] == "serve_named" and tr["order_seed"] == 40
    assert tr["arrivals"] == {"kind": "backlog", "requests": 1200}
    assert tr["prompt"] == dict(dist="lognormal", median=512, sigma=0.7,
                                lo=128, hi=2048, levels=16)
    assert tr["output"] == dict(dist="lognormal", median=512, sigma=0.6,
                                lo=128, hi=2048, levels=16)
    assert tr["shared_prefix"] == 0.0 and tr["warm_resumed"] is False
    importlib.import_module("chipbench.drivers.serve_named")
    for key in ("weights", "reference", "costs"):
        importlib.import_module("chipbench." + parts["config"]["modules"][key])
    assert {m["name"] for m in parts["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    names = {s["name"] for s in parts["layer_metrics"]}
    assert set(FALCON_METRICS) | set(FALCON_JOINED) | {
        "compile_s", "compiles_in_window"} <= names
    for m in manifest["per_layer"]:
        if m["name"] in FALCON_METRICS:
            assert m["workloads"] == [FALCON_CELL]
            assert m["moves"] == "serve_tokens_per_s"
        if m["name"] in FALCON_JOINED:
            assert FALCON_CELL in m["workloads"]
    cells = manifest["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_falcon_cell_is_found_whole():
    falcon_cell_found_whole(FALCON_MANIFEST)


def test_falcon_reference_imports_nothing_from_the_program():
    import ast
    for name in ("reference_falcon_h1", "weights_falcon_h1",
                 "costs_falcon_h1", os.path.join("reducers", "state_costs")):
        tree = ast.parse(open(os.path.join(FALCON_BENCH,
                                           name + ".py")).read())
        mods = [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)] + \
            [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
        assert not any(m.startswith("horovod_tpu") for m in mods), name
    src = open(os.path.join(FALCON_BENCH, "reference_falcon_h1.py")).read()
    assert "ssd_chunked" not in src and "lax.scan" in src  # the recurrence
