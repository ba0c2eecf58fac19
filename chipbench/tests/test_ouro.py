"""Tests of what the Ouro cell adds to the yardstick; on the CPU.

The configuration file against the published ``config.json``, the costs
against counts from the leaves and by hand, the comparison at a tiny size
(a sound run passes, the fp8 control does not), the new reducers on a
hand-built trace, and the manifest's new pieces found by name.  Names
here differ from those of the other files in this directory:
``tests/test_chipbench.py`` loads them all into one namespace.
"""

from __future__ import annotations

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import costs_ouro as ocosts                     # noqa: E402
from chipbench import peaks as opeaks                          # noqa: E402
from chipbench import run as runmod                            # noqa: E402
from chipbench import weights_ouro as oweights                 # noqa: E402

OURO_BENCH = os.path.join(ROOT, "chipbench")
OURO_MANIFEST = runmod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
OURO_TINY = runmod.load_json(os.path.join(
    OURO_BENCH, "testdata", "tiny_ouro", "BENCHMARK.json"))
OURO_CELL = "ouro-serve-reason-backlog"
OURO_CONFIG = runmod.load_json(os.path.join(
    OURO_BENCH, "configs", "ouro-2.6b-serve.json"))
OURO_DIMS = oweights.dims_of(OURO_CONFIG)
OURO_METRICS = ("serve_step_mfu.ouro", "decode_tick_roofline.ouro",
                "paged_decode_roofline.ouro", "kv_pool_fill.ouro",
                "reprefill_token_share.ouro")

#: config.json of ByteDance/Ouro-2.6B as the catalog copies it
#: (``layer_types`` is 48 times "full_attention")
OURO_PUBLISHED = dict(
    head_dim=128, hidden_act="silu", hidden_size=2048,
    intermediate_size=5632, layer_types=["full_attention"] * 48,
    max_position_embeddings=65536, max_window_layers=48, model_type="ouro",
    num_attention_heads=16, num_hidden_layers=48, num_key_value_heads=16,
    rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
    sliding_window=None, tie_word_embeddings=False, total_ut_steps=4,
    early_exit_threshold=1, use_sliding_window=False, vocab_size=49152)


# -- the configuration ------------------------------------------------------------

def test_ouro_config_keeps_every_published_number():
    entry = {c["name"]: c for c in OURO_MANIFEST["configs"]}[
        "ouro-2.6b-serve"]
    assert entry["source"] == OURO_CONFIG["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    changed = [k for k, v in OURO_PUBLISHED.items() if OURO_CONFIG[k] != v]
    assert changed == entry["reduced"] == OURO_CONFIG["reduced"] == []
    assert OURO_CONFIG["published"] == {}
    assert OURO_CONFIG["torch_dtype"] == "bfloat16"
    assert OURO_CONFIG["attention_path"] == "pallas"
    for what in ("layer", "loop", "cache", "exit", "rope"):
        assert what in OURO_CONFIG["assumed"]


def test_ouro_pool_and_weights_fill_the_chip_as_the_file_says():
    eng = OURO_CONFIG["engine"]
    per_token = ocosts.kv_bytes_per_token(OURO_DIMS)
    assert per_token == 2 * 16 * 128 * 2 * 192 == 1_572_864
    pool = eng["num_blocks"] * eng["block_size"] * per_token
    weights = 2 * oweights.parameter_count(OURO_DIMS)
    chip = 16 * 2 ** 30
    assert 0.75 < (pool + weights) / chip < 0.9
    # the longest context of the mix fits the pool several times over
    assert (eng["num_blocks"] - 1) * eng["block_size"] >= 5 * 1024
    assert max(eng["prefill_buckets"]) >= 512 + 512 - 1


# -- costs, against the leaves and by hand ---------------------------------------

def test_ouro_parameter_count_from_the_leaves():
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert oweights.parameter_count(OURO_DIMS) == \
        48 * layer + 2 * 2048 * 49152 + 2048 + 2049 == 2_667_974_657
    # the matrices alone, as the costs count them
    assert ocosts.stack_weight_count(OURO_DIMS) == 48 * (layer - 4 * 2048)
    assert ocosts.cache_layers(OURO_DIMS) == 192


def test_ouro_costs_count_every_pass():
    d = OURO_DIMS
    stack = ocosts.stack_weight_count(d)
    assert ocosts.matmul_flops_per_token(d) == \
        2 * (4 * stack + 2048 * 49152)
    assert round(ocosts.matmul_flops_per_token(d) / 1e9, 1) == 19.9
    # a prompt of 100: causal pairs in all 192 cache layers, head once
    flops, bytes_ = ocosts.prefill_cost(d, 100)
    assert flops == 2 * 4 * stack * 100 + 192 * 4 * 16 * 128 * 5050 \
        + 2 * 2048 * 49152
    assert bytes_ == 2 * (4 * stack + 2048 * 49152) + 100 * 1_572_864
    # a tick of two streams, 200 and 300 keys: 13 + 19 pages of 16 tokens
    flops, bytes_ = ocosts.decode_tick_cost(d, [200, 300], 16)
    assert flops == 2 * 4 * stack * 2 + 192 * 4 * 16 * 128 * 500 \
        + 2 * 2048 * 49152 * 2
    layer_pages = 2 * 32 * 16 * 16 * 128 * 2
    assert ocosts.paged_decode_bytes(d, [200, 300], 16) == layer_pages
    assert bytes_ == 2 * (4 * stack + 2048 * 49152) + 192 * layer_pages
    pk = opeaks.PEAKS["TPU v5 lite"]
    assert ocosts.least_seconds(flops, bytes_, pk) == \
        bytes_ / pk["hbm_bytes_per_s"]          # far on the bandwidth side


# -- the comparison, at a size a test can hold -----------------------------------

@pytest.fixture(scope="module")
def ouro_tiny_run():
    return runmod.execute(OURO_TINY, "tiny-ouro", 2 ** 31 + 7, 3.0, False,
                          require_tpu=False, control=True)


def test_ouro_tiny_sound_run_is_correct_and_preempts(ouro_tiny_run):
    out = ouro_tiny_run
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {
        "requests_failed", "answers_altered", "served_gap_max",
        "exit_sum_before_last_pass"}
    assert 0.0 < out["checks"]["exit_sum_before_last_pass"]["value"] < 1.0


def test_ouro_tiny_fp8_control_is_over_the_limit(ouro_tiny_run):
    assert ouro_tiny_run["controls"]["fp8"]["served_gap_max"] > \
        ouro_tiny_run["checks"]["served_gap_max"]["limit"]


def test_ouro_tiny_altered_token_is_not_correct(monkeypatch):
    from horovod_tpu.serving import engine as eng
    real = eng.ServingEngine._emit

    def altered(self, req, token):
        return real(self, req, (token + 1) % self.cfg.vocab_size
                    if len(req.generated) == 2 else token)
    monkeypatch.setattr(eng.ServingEngine, "_emit", altered)
    bad = runmod.execute(OURO_TINY, "tiny-ouro", 11, 3.0, False,
                         require_tpu=False)
    assert not bad["correct"], bad["checks"]


def test_ouro_driver_warms_every_shape_a_resumed_prefill_can_have():
    from chipbench.drivers import serve, serve_ouro
    parts = runmod.load_cell(OURO_MANIFEST, OURO_CELL)
    eng, tr = parts["config"]["engine"], parts["traffic"]
    lens = serve._warm_lengths(tr, eng["block_size"])
    more = serve_ouro._resumed_lengths(tr, eng, lens)
    bucket = lambda n: next(b for b in eng["prefill_buckets"] if n <= b)
    shape = lambda n: (bucket(n), -(-n // 16))
    warmed = {shape(n) for n in lens + more}
    assert {shape(n) for n in range(65, 489 + 512)} <= warmed
    assert len(more) == len({shape(n) for n in more}) < 64
    # no warm-up context needs a table wider than the window's widest
    assert max(lens + more) + 2 <= 64 * 16


# -- the new reducers, on a hand-built trace -------------------------------------

def ouro_hand_run():
    """Two traced turns in a window of 10 s: a first prefill of 100 tokens
    with a tick of two streams (200 and 300 keys), then a resumed prefill
    of 150 tokens with the same tick; the decode program ran twice for 50
    ms, the paged kernel 384 times for 4 ms in all; the pool stood at 300
    and 320 of 335 blocks."""
    kernel = "%hvd_paged_decode.5 = bf16[32,16,128]{2,1,0} custom-call()"
    ops = [(0, kernel, 1.0 + 0.0001 * i, 4e-3 / 384) for i in range(192)] + \
        [(0, kernel, 2.0 + 0.0001 * i, 4e-3 / 384) for i in range(192)] + \
        [(0, "%fusion.1 = bf16[8] fusion(%hvd_paged_decode.5)", 1.04, 0.01)]
    red = {"ops": ops, "devices": [0], "lo": 0.0, "hi": 10.0,
           "window_s": 10.0, "busy_s": 0.1,
           "modules": [(0, "jit_hvd_serve_decode(7)", 1.0, 0.05),
                       (0, "jit_hvd_serve_decode(7)", 2.0, 0.05),
                       (0, "jit_hvd_serve_prefill(3)", 0.9, 0.03)],
           "spans": [("engine.step", 0.8, 0.4), ("engine.step", 1.9, 0.3)],
           "hvd_spans": [
               ("hvd.serve.prefill", 0.8, 0.1, dict(tokens=100, resumed=0)),
               ("hvd.serve.decode", 1.0, 0.1,
                dict(blocks_held=300, blocks_usable=335)),
               ("hvd.serve.prefill", 1.9, 0.1, dict(tokens=150, resumed=150)),
               ("hvd.serve.decode", 2.0, 0.1,
                dict(blocks_held=320, blocks_usable=335))]}
    counters = {"profile_span": (0.5, 9.0), "block_size": 16, "steps": [
        dict(t0=0.1, t1=0.2, prefill=[64], resumed=[], decode=[]),  # before
        dict(t0=0.8, t1=1.2, prefill=[100], resumed=[], decode=[200, 300]),
        dict(t0=1.9, t1=2.2, prefill=[], resumed=[150], decode=[200, 300])]}
    return red, counters


OURO_BY_HAND = {
    # (prefill of 100 + two ticks) FLOPs over 197e12 over 10 s
    "serve_step_mfu.ouro": lambda d: 100.0 * (
        ocosts.prefill_cost(d, 100)[0]
        + 2 * ocosts.decode_tick_cost(d, [200, 300], 16)[0]) / 197e12 / 10.0,
    # two ticks' least bytes over 819e9, over 0.1 s of the decode program
    "decode_tick_roofline.ouro": lambda d: 100.0 * 2 * ocosts.decode_tick_cost(
        d, [200, 300], 16)[1] / 819e9 / 0.1,
    # 192 layers x 4,194,304 B a tick, two ticks, over 819e9, over 4 ms
    "paged_decode_roofline.ouro": lambda d: 100.0 * 2 * 192 * 4_194_304
    / 819e9 / 4e-3,
    "kv_pool_fill.ouro": lambda d: 100.0 * 620 / 670,
    "reprefill_token_share.ouro": lambda d: 100.0 * 150 / 250,
}


@pytest.mark.parametrize("metric", OURO_METRICS)
def test_ouro_reducer_on_the_hand_built_trace(metric):
    spec = runmod.load_json(os.path.join(OURO_BENCH, "layer_metrics",
                                         metric + ".json"))
    red, counters = ouro_hand_run()
    cell = {"spec": spec, "config": OURO_CONFIG, "cell": {"name": OURO_CELL},
            "peaks": opeaks.PEAKS["TPU v5 lite"], "chips": 1}
    reducer = importlib.import_module("chipbench.reducers." + spec["reducer"])
    got = reducer.reduce(red, counters, cell)
    assert got == pytest.approx(OURO_BY_HAND[metric](OURO_DIMS), rel=1e-9)
    assert 0.0 < got < 100.0
    # a program without the names or the attributes: nothing, no raise
    red, _ = ouro_hand_run()
    bare = dict(red, ops=[o for o in red["ops"] if "fusion" in o[1]],
                modules=red["modules"][2:], hvd_spans=[
                    (n, t, d, {"tokens": 1}) for n, t, d, _ in
                    red["hvd_spans"] if n.endswith("prefill")])
    quiet = reducer.reduce(bare, dict(counters, steps=[]), cell)
    assert quiet is None


# -- the manifest's new pieces, found by name --------------------------------------

def test_ouro_cell_is_found_whole():
    parts = runmod.load_cell(OURO_MANIFEST, OURO_CELL)
    assert parts["cell"]["chips"] == 1
    assert parts["config"]["total_ut_steps"] == 4
    tr = parts["traffic"]
    assert tr["driver"] == "serve_ouro" and tr["order_seed"] == 33
    assert tr["arrivals"] == {"kind": "backlog", "requests": 600}
    assert tr["prompt"] == dict(dist="lognormal", median=160, sigma=0.6,
                                lo=64, hi=512, levels=16)
    assert tr["output"] == dict(dist="lognormal", median=192, sigma=0.6,
                                lo=64, hi=512, levels=16)
    assert tr["profile"] == {"start_s": 8.0, "seconds": 3.0}
    assert tr["check_requests"] == 4 and tr["block"] == 16
    importlib.import_module("chipbench.drivers.serve_ouro")
    assert {m["name"] for m in parts["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    names = {s["name"] for s in parts["layer_metrics"]}
    assert set(OURO_METRICS) <= names
    assert {"batch_occupancy.backlog", "paged_table_fill.backlog",
            "decode_program_ms_p50.backlog", "compile_s"} <= names
    # the metrics that still tell Mistral's programs by shape stay its own
    assert not names & {"serve_step_mfu.backlog",
                        "prefill_device_ms_per_ktok.backlog",
                        "paged_decode_roofline.backlog"}
    for m in OURO_MANIFEST["per_layer"]:
        if m["name"] in OURO_METRICS:
            assert m["workloads"] == [OURO_CELL]
            assert m["moves"] == "serve_tokens_per_s"


def test_ouro_reference_imports_nothing_from_the_program():
    import ast
    for name in ("reference_ouro", "weights_ouro", "costs_ouro"):
        tree = ast.parse(open(os.path.join(OURO_BENCH, name + ".py")).read())
        mods = [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)] + \
            [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
        assert not any(m.startswith("horovod_tpu") for m in mods), name
    src = open(os.path.join(OURO_BENCH, "reference_ouro.py")).read()
    assert 'precision="highest"' in src
