"""Tests of the benchmark's own yardstick; seconds on the CPU.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

The trace reduction on a hand-built trace, ``costs.py`` against
hand-worked numbers, the generator, the manifest, the control (the fp8
reference has to fail the comparison) and the planted faults (a run with
the timed path broken underneath has to come out not correct), all at a
size a test run can hold.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import costs, loadgen, run as runmod, trace  # noqa: E402

BENCH = os.path.join(ROOT, "chipbench")
MANIFEST = runmod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TINY = runmod.load_json(os.path.join(BENCH, "testdata", "tiny",
                                     "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MISTRAL = dict(d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
               d_ff=14336, n_layers=5, vocab_size=32768)


# -- trace reduction ----------------------------------------------------------

def hand_trace():
    return json.load(open(os.path.join(BENCH, "testdata",
                                       "hand_trace.json")))


def as_events(t):
    return {k: [tuple(e) for e in t[k]] for k in ("ops", "modules", "spans")}


def test_trace_busy_idle_and_window():
    red = trace.reduce_events(as_events(hand_trace()))
    # spans cover 0.0-1.0; device 0 busy 0.1-0.3, 0.3-0.4, 0.6-0.9
    assert red["window_s"] == pytest.approx(1.0)
    assert red["busy_s"] == pytest.approx(0.6)


def test_trace_gaps_named_by_innermost_span():
    red = trace.reduce_events(as_events(hand_trace()))
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["feed"] == pytest.approx(0.1)          # 0.0-0.1
    assert gaps["engine.step"] == pytest.approx(0.2)   # 0.4-0.6
    assert gaps["(no span)"] == pytest.approx(0.1)     # 0.9-1.0 uncovered


def test_trace_kernel_time_by_pattern_and_program():
    t = as_events(hand_trace())
    red = dict(trace.reduce_events(t), **t)
    s, n = trace.op_seconds(red, ["mosaic"])
    assert (s, n) == (pytest.approx(0.5), 2)
    s, n = trace.op_seconds(red, ["mosaic"], ["jit_decode"])
    assert (s, n) == (pytest.approx(0.3), 1)
    assert len(trace.module_runs(red, ["jit_prefill"])) == 1


def test_union_length_overlaps_and_clips():
    busy, gaps = trace.union_length([(0, 2), (1, 3), (5, 9)], 0.5, 8)
    assert busy == pytest.approx(5.5)
    assert gaps == [(3, 5)]


# -- costs ----------------------------------------------------------------------

def test_costs_one_mistral_layer():
    # 4096*4096 (q) + 2*4096*1024 (k, v) + 4096*4096 (o) + 3*4096*14336
    assert costs.layer_weight_count(MISTRAL) == 218_103_808


def test_costs_train_flops_per_token():
    # forward: 5 layers x 2 x 218.1 M + 5 x 4 x 4096 x 1024.5 attended
    # pairs + head 2 x 134.2 M; times 3 for the backward
    fwd = 5 * 2 * 218_103_808 + 5 * 4 * 4096 * 1024.5 + 2 * 4096 * 32768
    assert costs.train_flops_per_token(MISTRAL, 2048) == pytest.approx(3 * fwd)


def test_costs_paged_decode_call():
    # lengths 17 and 16 -> 2 + 1 pages of 16 x 8 x 128 bf16, K and V
    assert costs.paged_decode_bytes(MISTRAL, [17, 16], 16) == \
        3 * 16 * 8 * 128 * 2 * 2


def test_costs_flash_call():
    f = costs.flash_flops(MISTRAL, 4, 2048)
    pair = 2 * 4 * 32 * 128 * 2048 * 2049 / 2
    assert f == {"fwd": 2 * pair, "dq": 3 * pair, "dkv": 4 * pair}


def test_costs_decode_tick_is_memory_bound():
    pk = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, byts = costs.decode_tick_cost(dict(MISTRAL, n_layers=16),
                                         [1000] * 16, 16)
    assert byts / pk["hbm_bytes_per_s"] > flops / pk["flops_per_s"]
    assert costs.least_seconds(flops, byts, pk) == byts / 819e9


# -- the generator ---------------------------------------------------------------

def traffic(name, root=BENCH):
    return runmod.load_json(os.path.join(root, "traffic", name + ".json"))


SERVE_MIXES = sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
    if traffic(f[:-5])["driver"] == "serve")


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_generator_same_seed_same_schedule(mix):
    a = loadgen.schedule(traffic(mix), 2**31 + 7, 32768, 20.0)
    b = loadgen.schedule(traffic(mix), 2**31 + 7, 32768, 20.0)
    c = loadgen.schedule(traffic(mix), 2**31 + 8, 32768, 20.0)
    assert len(a) == len(b) > 0
    assert all(x["due_s"] == y["due_s"] and x["max_tokens"] == y["max_tokens"]
               and np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    assert any(len(x["prompt"]) != len(y["prompt"])
               or not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_generator_lengths_clipped_and_every_seed_same_mix(mix):
    tr = traffic(mix)
    per_seed = []
    for seed in (1, 2, 3):
        reqs = loadgen.schedule(tr, seed, 32768, 20.0)
        n = len(reqs) // tr["block"] * tr["block"]
        p = [len(r["prompt"]) for r in reqs[:n]]
        o = [r["max_tokens"] for r in reqs[:n]]
        assert tr["prompt"]["lo"] <= min(p) and max(p) <= tr["prompt"]["hi"]
        assert tr["output"]["lo"] <= min(o) and max(o) <= tr["output"]["hi"]
        assert set(p) <= set(loadgen.levels(tr["prompt"]))
        assert all(a["due_s"] <= b["due_s"] for a, b in zip(reqs, reqs[1:]))
        per_seed.append((n, sum(p), sum(o)))
    if tr["arrivals"]["kind"] == "backlog":
        # one level per stratum: every seed sends the same multiset
        if len(loadgen.levels(tr["prompt"])) == tr["block"]:
            assert len(set(per_seed)) == 1


def test_generator_token_batches():
    feed = loadgen.TokenBatches(2**31 + 5, 4, 33, 256)
    assert np.array_equal(feed.batch(3), feed.batch(3))
    assert not np.array_equal(feed.batch(3), feed.batch(4))
    assert len({r.tobytes() for r in feed.batch(0)}) == 4   # rows differ
    assert feed.batch(0).dtype == np.int32 and feed.batch(0).max() < 256


def test_serve_percentile_is_a_tail_of_all_requests():
    from chipbench.drivers.serve import percentile
    assert percentile(list(range(1, 101)), 90) == 90
    assert percentile([1.0] * 8 + [np.inf] * 2, 90) == np.inf  # misses count


# -- the manifest ------------------------------------------------------------------

CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_manifest_cell_parts_found_by_name(cell):
    parts = runmod.load_cell(MANIFEST, cell)
    assert parts["config"]["source"].startswith("https://")
    assert os.path.exists(os.path.join(
        BENCH, "drivers", parts["traffic"]["driver"] + ".py"))
    names = {m["name"] for m in parts["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert parts["layer_metrics"]
    for spec in parts["layer_metrics"]:
        assert os.path.exists(os.path.join(
            BENCH, "reducers", spec["reducer"] + ".py"))


def test_manifest_names_units_and_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_manifest_layer_metric_moves_what_its_cells_report(metric):
    m = {x["name"]: x for x in MANIFEST["per_layer"]}[metric]
    spec = runmod.load_json(os.path.join(BENCH, "layer_metrics",
                                         metric + ".json"))
    for key in ("unit", "layer", "moves"):
        assert spec[key] == m[key]
    e2e = {x["name"]: x for x in MANIFEST["end_to_end"]}[m["moves"]]
    reported_in = set(e2e.get("workloads", CELLS))
    assert set(m.get("workloads", CELLS)) <= reported_in
    if "mfu" not in metric and metric.endswith("_roofline") is False:
        return
    assert m["unit"] == "%"


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_manifest_config_keeps_published_widths(config):
    entry = {c["name"]: c for c in MANIFEST["configs"]}[config]
    cfg = runmod.load_json(os.path.join(ROOT, entry["file"]))
    published = dict(hidden_size=4096, intermediate_size=14336,
                     num_attention_heads=32, num_key_value_heads=8,
                     head_dim=128, vocab_size=32768, rope_theta=1e6,
                     rms_norm_eps=1e-5, num_hidden_layers=32)
    changed = [k for k, v in published.items() if cfg[k] != v]
    assert changed == entry["reduced"] == cfg["reduced"]


# -- the command -----------------------------------------------------------------

def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


# -- the control and the planted faults, at a size a test can hold ---------------

def tiny_run(cell, seed=11, control=False):
    return runmod.execute(TINY, cell, seed, 0.5, False, require_tpu=False,
                          control=control)


def test_tiny_train_cell_is_correct_and_control_fails():
    out = tiny_run("tiny-train", control=True)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    lim = {k: v["limit"] for k, v in out["checks"].items()}
    for name in ("fp8", "half_batch"):
        reading = out["controls"][name]
        assert any(reading[k] > lim[k] for k in reading if k in lim), \
            (name, reading)


FAULTS = {
    "state_unchanged": lambda p, st, tok, call: (p, st, call(p, st, tok)[2]),
    "half_batch": lambda p, st, tok, call: call(
        p, st, np.concatenate([np.asarray(tok)[:2]] * 2)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_tiny_train_fault_comes_out_not_correct(fault, monkeypatch):
    from horovod_tpu.models import llama
    real = llama.make_train_step

    def broken(cfg, mesh, tx, **kw):
        step = real(cfg, mesh, tx, **kw)
        if fault == "state_unchanged":
            # donation would invalidate the state we hand back unchanged
            import jax
            step = jax.jit(step.__wrapped__)
        return lambda p, st, batch: FAULTS[fault](
            p, st, batch["tokens"],
            lambda p_, st_, t_: step(p_, st_, {"tokens": t_}))
    monkeypatch.setattr(llama, "make_train_step", broken)
    out = tiny_run("tiny-train")
    assert not out["correct"], out["checks"]


def test_tiny_hvd_without_the_exchange_is_not_correct(monkeypatch):
    import horovod_tpu as hvd
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    ok = tiny_run("tiny-hvd")
    assert ok["correct"], ok["checks"]
    monkeypatch.setattr(hvd, "DistributedOptimizer", lambda inner, **kw: inner)
    out = tiny_run("tiny-hvd")
    assert not out["correct"], out["checks"]


def test_tiny_serve_cell_correct_control_and_altered_token(monkeypatch):
    out = tiny_run("tiny-backlog", control=True)
    assert out["correct"], out["checks"]
    assert out["controls"]["fp8"]["served_gap_max"] > \
        out["checks"]["served_gap_max"]["limit"]

    from horovod_tpu.serving import engine as eng
    real = eng.ServingEngine._emit

    def altered(self, req, token):
        return real(self, req, (token + 1) % self.cfg.vocab_size
                    if len(req.generated) == 2 else token)
    monkeypatch.setattr(eng.ServingEngine, "_emit", altered)
    bad = tiny_run("tiny-backlog")
    assert not bad["correct"], bad["checks"]


def test_tiny_open_loop_judges_every_request_due_in_the_window():
    out = tiny_run("tiny-chat")
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 5 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_ms_p90", "tpot_ms_p90", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
