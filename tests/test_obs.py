"""Observability plane: metrics registry, exposition, HTTP endpoint,
Timeline v2 (counter + flow events), the cross-layer wiring, and the
distributed plane (cross-rank aggregation, straggler attribution,
multi-rank timeline merge).

The registry/export tests run on private ``MetricRegistry`` instances so
they are deterministic regardless of what the session's engine has
already recorded into the process-wide default registry; the wiring
tests drive the real engine/serving paths and only assert deltas; the
``integration``-marked tests launch real hvdrun jobs.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.obs import (
    REGISTRY,
    MetricError,
    MetricRegistry,
    aggregate,
    export,
    flightrec,
    server,
    slo,
    trace,
)
from horovod_tpu.utils.timeline import Timeline, merge_timelines

N = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_concurrent_increments():
    reg = MetricRegistry()
    c = reg.counter("t_events_total")
    per_thread = 5000

    def work():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8 * per_thread


def test_counter_rejects_negative_and_gauge_moves_both_ways():
    reg = MetricRegistry()
    with pytest.raises(MetricError):
        reg.counter("c_total").inc(-1)
    g = reg.gauge("g")
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value == 4


def test_histogram_bucket_edges_are_inclusive_upper_bounds():
    reg = MetricRegistry()
    h = reg.histogram("h_seconds", buckets=(1.0, 2.0, 4.0))
    for v in (1.0, 2.0, 2.0001, 5.0):   # edge, edge, just-over, overflow
        h.observe(v)
    [sample] = reg.snapshot()[0]["samples"]
    assert sample["buckets"] == [(1.0, 1), (2.0, 2), (4.0, 3),
                                 (float("inf"), 4)]
    assert sample["count"] == 4
    assert sample["sum"] == pytest.approx(10.0001)


def test_labels_kind_conflicts_and_reset():
    reg = MetricRegistry()
    c = reg.counter("req_total", labelnames=("verb",))
    c.labels(verb="a").inc(2)
    c.labels(verb="b").inc(3)
    assert c.total() == 5
    with pytest.raises(MetricError):
        c.inc()                      # labeled family needs .labels()
    with pytest.raises(MetricError):
        c.labels(wrong="x")
    with pytest.raises(MetricError):
        reg.gauge("req_total")       # kind conflict
    assert reg.counter("req_total", labelnames=("verb",)) is c  # idempotent
    reg.reset()
    assert c.total() == 0
    assert c.labels(verb="a").value == 0  # children survive reset


def test_disable_makes_recording_a_noop():
    reg = MetricRegistry()
    c = reg.counter("c_total")
    h = reg.histogram("h_seconds")
    reg.disable()
    c.inc()
    h.observe(1.0)
    reg.enable()
    c.inc()
    assert c.value == 1 and h.count == 0


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------

def _golden_registry() -> MetricRegistry:
    reg = MetricRegistry()
    c = reg.counter("req_total", "requests by code", ("code",))
    c.labels(code="200").inc(3)
    c.labels(code="500").inc()
    reg.gauge("depth", "queue depth").set(2.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    return reg


GOLDEN = """\
# HELP depth queue depth
# TYPE depth gauge
depth 2.5
# HELP lat_seconds latency
# TYPE lat_seconds histogram
lat_seconds_bucket{le="0.1"} 1
lat_seconds_bucket{le="1"} 2
lat_seconds_bucket{le="+Inf"} 2
lat_seconds_sum 0.55
lat_seconds_count 2
# HELP req_total requests by code
# TYPE req_total counter
req_total{code="200"} 3
req_total{code="500"} 1
"""


def test_prometheus_golden_text():
    text = export.to_prometheus(_golden_registry().snapshot())
    assert text == GOLDEN
    export.validate_prometheus(text)


def test_json_exposition_parses_and_matches():
    blob = json.loads(export.to_json(_golden_registry().snapshot()))
    fams = {m["name"]: m for m in blob["metrics"]}
    assert fams["req_total"]["samples"][0]["value"] == 3
    hist = fams["lat_seconds"]["samples"][0]
    assert hist["count"] == 2 and hist["buckets"][-1] == ["+Inf", 2]


def test_validate_catches_malformed_exposition():
    with pytest.raises(ValueError):
        export.validate_prometheus("no_type_header 1\n")
    with pytest.raises(ValueError):
        export.validate_prometheus("# TYPE x counter\nx 1 2 3\n")


def test_http_endpoint_roundtrip():
    reg = _golden_registry()
    srv = server.MetricsServer(0, addr="127.0.0.1", registry=reg)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        resp = urllib.request.urlopen(f"{base}/metrics", timeout=10)
        assert resp.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        text = resp.read().decode()
        assert text == GOLDEN
        export.validate_prometheus(text)
        blob = json.loads(urllib.request.urlopen(
            f"{base}/metrics.json", timeout=10).read().decode())
        assert {m["name"] for m in blob["metrics"]} == \
            {"req_total", "depth", "lat_seconds"}
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=10)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Timeline v2
# ---------------------------------------------------------------------------

def test_timeline_v2_counter_and_flow_events(tmp_path):
    path = tmp_path / "tl.json"
    with Timeline(str(path)) as tl:
        tl.start_activity("tensor", "QUEUE")
        fid = tl.new_flow()
        tl.flow_start("tensor", fid)
        tl.end_activity("tensor")
        tl.start_activity("tensor", "DISPATCH")
        tl.flow_end("tensor", fid)
        tl.counter("hvd.engine", {"queue_depth": 3, "bytes": 16.0})
        tl.end_activity("tensor")
    events = json.loads(path.read_text())     # Perfetto-parseable JSON
    by_ph = {}
    for ev in events:
        by_ph.setdefault(ev["ph"], []).append(ev)
    assert by_ph["s"][0]["id"] == fid and by_ph["s"][0]["cat"] == "flow"
    assert by_ph["f"][0]["id"] == fid and by_ph["f"][0]["bp"] == "e"
    assert by_ph["C"][0]["args"] == {"queue_depth": 3, "bytes": 16.0}
    assert len(by_ph["B"]) == 2 and len(by_ph["E"]) == 2


def test_timeline_flush_survives_without_close(tmp_path):
    path = tmp_path / "tl.json"
    tl = Timeline(str(path))
    tl.start_activity("t", "QUEUE")
    tl.flush()
    raw = path.read_text()
    assert '"QUEUE"' in raw                   # on disk before close
    # Chrome/Perfetto accept the truncated array (no closing bracket);
    # emulate that tolerance to prove the tail parses.
    events = json.loads(raw.rstrip().rstrip(",") + "]")
    assert any(ev.get("name") == "QUEUE" for ev in events)
    tl.close()


# ---------------------------------------------------------------------------
# cross-layer wiring
# ---------------------------------------------------------------------------

def test_engine_series_and_hvd_metrics_api(tmp_path):
    col = REGISTRY.get("hvd_collectives_total")
    byt = REGISTRY.get("hvd_collective_bytes_total")
    before_n, before_b = col.total(), byt.total()
    tl_path = tmp_path / "tl.json"
    hvd.start_timeline(str(tl_path))
    try:
        x = hvd.per_rank(
            [np.full((16,), float(r), np.float32) for r in range(N)])
        h = hvd.allreduce_async(x, hvd.Average, name="obs.t1")
        hvd.synchronize(h)
    finally:
        hvd.stop_timeline()
    assert col.total() == before_n + 1
    assert byt.total() == before_b + N * 16 * 4
    events = json.loads(tl_path.read_text())
    phs = {ev["ph"] for ev in events}
    assert {"s", "f", "C"} <= phs             # flows + counter tracks
    counter_ev = next(ev for ev in events if ev["ph"] == "C")
    assert counter_ev["args"]["collectives_total"] >= 1
    # hvd.metrics(): all three formats over the same snapshot
    text = hvd.metrics("prometheus")
    export.validate_prometheus(text)
    assert "hvd_collectives_total" in text
    assert "hvd_dispatch_cache_hits_total" in text
    names = {m["name"] for m in hvd.metrics()}
    assert "hvd_collective_bytes_total" in names
    json.loads(hvd.metrics("json"))
    with pytest.raises(ValueError):
        hvd.metrics("xml")


# ---------------------------------------------------------------------------
# distributed plane: aggregation, /cluster, straggler attribution,
# timeline merge
# ---------------------------------------------------------------------------

def test_merge_snapshots_sums_counters_and_labels_ranks():
    regs = []
    for r in range(2):
        reg = MetricRegistry()
        reg.counter("m_events_total", "ev", ("kind",)) \
            .labels(kind="x").inc(r + 1)
        reg.gauge("m_depth").set(r * 5)
        reg.histogram("m_lat_seconds", buckets=(0.1, 1.0)) \
            .observe(0.05 * (r + 1))
        regs.append(reg)
    snaps = [json.loads(aggregate.local_snapshot_blob(
        r, 2, registry=reg).decode()) for r, reg in enumerate(regs)]
    merged = aggregate.merge_snapshots(snaps)
    text = export.to_prometheus(merged)
    export.validate_prometheus(text)
    assert 'm_events_total{kind="x",rank="0"} 1' in text
    assert 'm_events_total{kind="x",rank="1"} 2' in text
    assert 'm_events_total{kind="x"} 3' in text          # cluster sum
    import re
    assert 'm_depth{rank="0"} 0' in text                 # gauges per-rank
    assert 'm_depth{rank="1"} 5' in text
    assert not re.search(r"^m_depth \d", text, re.M)     # no gauge sum
    assert 'm_lat_seconds_count{rank="0"} 1' in text
    assert "m_lat_seconds_count 2" in text               # bucket merge
    assert "horovod_tpu_cluster_ranks_reporting 2" in text
    json.loads(export.to_json(merged))                   # strict JSON


def test_merge_keeps_families_with_own_rank_label_distinct():
    """A family that already owns a 'rank' label (the straggler gauge:
    rank = the straggler) must not collapse into duplicate series when
    several ranks report it — the reporting rank goes to 'from_rank'."""
    regs = []
    for r in range(2):
        reg = MetricRegistry()
        reg.gauge("straggler_age", "g", ("rank", "tensor")) \
            .labels(rank="3", tensor="t").set(10.0 + r)
        regs.append(reg)
    merged = aggregate.merge_snapshots([
        json.loads(aggregate.local_snapshot_blob(
            r, 2, registry=reg).decode())
        for r, reg in enumerate(regs)])
    text = export.to_prometheus(merged)
    export.validate_prometheus(text)
    [fam] = [f for f in merged if f["name"] == "straggler_age"]
    assert "from_rank" in fam["labelnames"]
    series = {(s["labels"]["rank"], s["labels"]["from_rank"]): s["value"]
              for s in fam["samples"]}
    assert series == {("3", "0"): 10.0, ("3", "1"): 11.0}


def test_merge_skips_cluster_histogram_on_divergent_buckets():
    r0, r1 = MetricRegistry(), MetricRegistry()
    r0.histogram("h_seconds", buckets=(0.1, 1.0)).observe(0.5)
    r1.histogram("h_seconds", buckets=(0.2, 2.0)).observe(0.5)
    merged = aggregate.merge_snapshots([
        json.loads(aggregate.local_snapshot_blob(
            r, 2, registry=reg).decode())
        for r, reg in enumerate((r0, r1))])
    [fam] = [f for f in merged if f["name"] == "h_seconds"]
    # per-rank series survive; no merged (rank-less) series is fabricated
    # from incompatible bucket layouts.
    assert all("rank" in s["labels"] for s in fam["samples"])
    export.validate_prometheus(export.to_prometheus(merged))


def test_cluster_metrics_single_process_world():
    """No KV store: the cluster view is the local registry labeled
    rank=<this process> — world size 1, same shape as a real cluster."""
    snap = hvd.cluster_metrics()
    fams = {f["name"]: f for f in snap}
    assert "hvd_collectives_total" in fams
    assert all("rank" in s["labels"]
               for s in fams["hvd_engine_queue_depth"]["samples"])
    bi = fams["horovod_tpu_build_info"]
    live = [s for s in bi["samples"] if s["value"] == 1.0]
    assert live and live[0]["labels"]["version"] == hvd.__version__
    text = hvd.cluster_metrics("prometheus")
    export.validate_prometheus(text)
    assert "horovod_tpu_cluster_ranks_reporting 1" in text
    with pytest.raises(ValueError):
        hvd.cluster_metrics("xml")


def test_cluster_endpoint_served_next_to_metrics():
    """/cluster rides the same server as /metrics once init armed the
    provider (the conftest session already ran hvd.init())."""
    srv = server.MetricsServer(0, addr="127.0.0.1")
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(
            f"{base}/cluster", timeout=10).read().decode()
        export.validate_prometheus(text)
        assert 'rank="0"' in text
        blob = json.loads(urllib.request.urlopen(
            f"{base}/cluster.json", timeout=10).read().decode())
        assert any(m["name"] == "horovod_tpu_cluster_size"
                   for m in blob["metrics"])
    finally:
        srv.close()


def test_timeline_merge_one_pid_lane_per_rank(tmp_path):
    import time as _time
    paths = []
    for r in range(2):
        p = tmp_path / f"rank{r}.json"
        with Timeline(str(p), rank=r) as tl:
            tl.start_activity("grad.0", "QUEUE")
            fid = tl.new_flow()
            tl.flow_start("grad.0", fid)
            tl.end_activity("grad.0")
            tl.start_activity("grad.0", "DISPATCH")
            tl.flow_end("grad.0", fid)
            tl.counter("hvd.engine", {"queue_depth": r})
            tl.end_activity("grad.0")
        paths.append(str(p))
        _time.sleep(0.02)
    out = tmp_path / "merged.json"
    summary = merge_timelines(str(out), paths)
    assert summary["ranks"] == [0, 1]
    events = json.loads(out.read_text())
    # one pid lane per rank, named and sorted
    assert {e["pid"] for e in events if e["ph"] in "BEC"} == {0, 1}
    names = {e["pid"]: e["args"]["name"] for e in events
             if e.get("name") == "process_name"}
    assert names == {0: "rank 0", 1: "rank 1"}
    # flow arrows survive per rank without aliasing across ranks
    flow = {}
    for e in events:
        if e["ph"] in ("s", "f"):
            flow.setdefault(e["pid"], {})[e["ph"]] = e["id"]
    assert flow[0]["s"] == flow[0]["f"]
    assert flow[1]["s"] == flow[1]["f"]
    assert flow[0]["s"] != flow[1]["s"]
    # counter tracks land in their rank's lane
    assert {e["pid"] for e in events if e["ph"] == "C"} == {0, 1}
    # clock_sync rebase: rank 1 started later, so its spans sit later on
    # the shared axis even though both files' own ts start near 0.
    b0 = min(e["ts"] for e in events if e["pid"] == 0 and e["ph"] == "B")
    b1 = min(e["ts"] for e in events if e["pid"] == 1 and e["ph"] == "B")
    assert b1 > b0


def test_timeline_merge_cli_accepts_truncated_input(tmp_path):
    p0 = tmp_path / "rank0.json"
    tl = Timeline(str(p0), rank=0)
    tl.start_activity("t", "QUEUE")
    tl.flush()                      # crash-truncated: no closing bracket
    p1 = tmp_path / "rank1.json"
    with Timeline(str(p1), rank=1) as tl1:
        tl1.start_activity("t", "QUEUE")
        tl1.end_activity("t")
    out = tmp_path / "m.json"
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.utils.timeline", "merge",
         str(out), str(p0), str(p1)],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "PYTHONPATH":
             REPO + os.pathsep + os.environ.get("PYTHONPATH", "")})
    assert res.returncode == 0, res.stderr
    events = json.loads(out.read_text())
    assert {e["pid"] for e in events if e["ph"] == "B"} == {0, 1}
    tl.close()


def _hvdrun(np_, extra_env=None, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_), "--",
         sys.executable, os.path.join(REPO, "tests", "mp_obs_worker.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.mark.integration
def test_cluster_view_aggregates_both_ranks_np2():
    """Acceptance: rank 0's /cluster contains both ranks' counters summed
    and the rank label present (incl. SLO gauges + trace counters from
    both ranks), /healthz answers ready, and it validates as
    Prometheus."""
    res = _hvdrun(2)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(2):
        assert f"rank {r}: CLUSTER-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.slow
def test_cluster_serving_trace_e2e_np2():
    """Acceptance: same np=2 cluster pass but rank 0's sampled trace is
    one REAL serving request — connected QUEUE→PREFILL→DECODE chain
    sharing a trace id in the Timeline v2 output.  slow-marked for the
    tiny-llama compile; the in-process serving-trace test above covers
    the chain shape in tier-1."""
    res = _hvdrun(2, extra_env={"HVDTPU_OBS_SERVING_E2E": "1"})
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(2):
        assert f"rank {r}: CLUSTER-OK" in res.stdout, res.stdout


@pytest.mark.integration
def test_tsdb_alerts_and_query_over_cluster_np2():
    """Acceptance (tsdb tier): at np=2 with HVDTPU_ALERTS armed through
    the real config surface, a breached rule fires on BOTH ranks and the
    firing gauges arrive rank-labeled on /cluster; /alertz reports the
    firing state; /query answers over the local sampled history AND the
    fleet history fed by the /cluster merges; a flight-recorder bundle
    carries the alert_fired event and the curated tsdb tail."""
    res = _hvdrun(2, extra_env={"HVDTPU_TEST_MODE": "tsdb"})
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(2):
        assert f"rank {r}: TSDB-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.slow
def test_healthz_transitions_under_injected_faults_np2():
    """Acceptance (chaos satellite): with a fault spec stalling rank 1's
    negotiation check-in and then injecting a serving-step failure,
    rank 0's /healthz must transition 200 -> 503 -> 200 twice (stall,
    then serving drain window), the aborted request must carry
    finish_reason="error", and rank 1's injected fault must surface
    rank-labeled in hvd_faults_injected_total on /cluster.  slow-marked
    (two runner startups + a tiny-llama compile); the in-process halves
    are tier-1 in test_chaos.py."""
    res = _hvdrun(2, extra_env={
        "HVDTPU_TEST_MODE": "chaos",
        "HVDTPU_HEALTH_MAX_NEGOTIATION_AGE": "1",
    }, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "rank 0: CHAOS-OK" in res.stdout, res.stdout
    assert "rank 1: CHAOS-STALLER-OK" in res.stdout, res.stdout


@pytest.mark.integration
def test_straggler_attribution_np4():
    """Acceptance: a deliberately withheld allreduce at np=4 produces a
    stall report naming the exact lagging rank and tensor."""
    res = _hvdrun(4, extra_env={
        "HVDTPU_TEST_MODE": "stall",
        "HVDTPU_STALL_CHECK_TIME_SECONDS": "2",
        "HVDTPU_STALL_SHUTDOWN_TIME_SECONDS": "4",
    })
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(3):
        assert f"rank {r}: STRAGGLER-OK" in res.stdout, res.stdout
    assert "rank 3: STRAGGLER-BYSTANDER-OK" in res.stdout, res.stdout
    # the actionable log line names rank + tensor (+ age)
    assert "Straggler: rank(s) 3 have not submitted tensor " \
        "'t.straggle'" in res.stdout, res.stdout


# ---------------------------------------------------------------------------
# request tracing (obs/trace)
# ---------------------------------------------------------------------------

def test_trace_span_chain_export_and_keep_bound():
    tr = trace.Tracer(sample_rate=1.0, keep=4)
    root = tr.start_trace("req", lane="req0", req_id=0)
    q = root.child("QUEUE", prompt_len=5)
    q.end(queue_wait_s=0.0)
    p = root.child("PREFILL", after=q)
    p.event("collective.enqueue", tensor="wo.0")
    p.end()
    root.end(outcome="finished")
    exp = tr.export()
    assert exp["trace_id"] == root.trace_id
    by_name = {s["name"]: s for s in exp["spans"]}
    assert set(by_name) == {"QUEUE", "PREFILL", "req"}
    assert {s["trace_id"] for s in exp["spans"]} == {root.trace_id}
    assert by_name["req"]["parent_id"] is None
    assert by_name["QUEUE"]["parent_id"] == by_name["req"]["span_id"]
    assert by_name["PREFILL"]["parent_id"] == by_name["req"]["span_id"]
    assert by_name["QUEUE"]["attrs"]["queue_wait_s"] == 0.0
    assert by_name["PREFILL"]["events"][0]["name"] == "collective.enqueue"
    assert all(s["duration_s"] >= 0 for s in exp["spans"])
    json.dumps(exp)                        # JSON-exportable by contract
    # finished-trace table is bounded: oldest traces evicted first
    first_id = root.trace_id
    for _ in range(4):
        tr.start_trace("req").end()
    assert len(tr.finished_ids()) == 4
    assert first_id not in tr.finished_ids()
    assert tr.export(first_id) is None


def test_trace_context_propagation_and_idempotent_end():
    tr = trace.Tracer(sample_rate=1.0)
    assert trace.current_span() is None
    root = tr.start_trace("req")
    with root.use():
        assert trace.current_span() is root
        child = root.child("PREFILL")
        with child.use():
            assert trace.current_span() is child
        assert trace.current_span() is root
    assert trace.current_span() is None
    child.end()
    t1 = child.t1
    child.end(ignored=True)                # double-close: no-op
    assert child.t1 == t1 and "ignored" not in child.attrs
    root.end()


def test_trace_unsampled_is_null_span_noop():
    tr = trace.Tracer(sample_rate=0.0)
    sp = tr.start_trace("req")
    assert sp is trace.NULL_SPAN and not sp.sampled and not sp
    assert sp.child("QUEUE") is sp         # every op returns instantly
    with sp.use():
        assert trace.current_span() is None   # never leaks NULL_SPAN
    sp.event("x")
    sp.end()
    assert tr.export() is None and tr.finished_ids() == []


def test_trace_timeline_slices_and_flow_arrows(tmp_path):
    path = tmp_path / "tl.json"
    with Timeline(str(path)) as tl:
        tr = trace.Tracer(sample_rate=1.0)
        root = tr.start_trace("req", lane="req7", timeline=tl)
        q = root.child("QUEUE")
        q.end()
        p = root.child("PREFILL", after=q)  # flow arrow QUEUE -> PREFILL
        p.end()
        root.end()
    events = json.loads(path.read_text())
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"QUEUE", "PREFILL", "req"}
    assert {e["args"]["trace_id"] for e in xs} == {root.trace_id}
    assert all(e["dur"] >= 0 for e in xs)
    links = [e for e in events if e.get("name") == "hvd.link"]
    s = [e for e in links if e["ph"] == "s"]
    f = [e for e in links if e["ph"] == "f"]
    assert len(s) == 1 and len(f) == 1 and s[0]["id"] == f[0]["id"]
    assert f[0]["bp"] == "e"
    # arrow tail sits at QUEUE's end, head at PREFILL's start
    [qx] = [e for e in xs if e["name"] == "QUEUE"]
    [px] = [e for e in xs if e["name"] == "PREFILL"]
    assert s[0]["ts"] == pytest.approx(qx["ts"] + qx["dur"], abs=1.0)
    assert f[0]["ts"] == pytest.approx(px["ts"], abs=1.0)


def test_serving_trace_chain_and_greedy_parity():
    """One request -> one connected QUEUE->PREFILL->DECODE chain sharing
    a trace id; disabling sampling changes nothing about the tokens."""
    import jax

    from horovod_tpu import serving
    from horovod_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(7, dtype=np.int32)

    def run_once():
        with serving.serve(params, cfg, num_blocks=16, block_size=8,
                           max_active=2) as sess:
            fut = sess.submit(prompt, max_tokens=4)
            sess.drain()
            res = fut.result(timeout=30)
            return res, sess.request_trace(res.metrics["req_id"])

    old_rate = trace.TRACER.sample_rate
    try:
        trace.TRACER.sample_rate = 1.0
        res_on, tr = run_once()
        trace.TRACER.sample_rate = 0.0
        res_off, tr_off = run_once()
    finally:
        trace.TRACER.sample_rate = old_rate
    assert res_on.tokens == res_off.tokens          # greedy parity
    assert tr_off is None                           # unsampled: no trace
    assert tr is not None
    assert res_on.metrics["trace_id"] == tr["trace_id"]
    names = [s["name"] for s in tr["spans"]]
    assert {"QUEUE", "PREFILL", "DECODE", "serving.request"} <= set(names)
    assert {s["trace_id"] for s in tr["spans"]} == {tr["trace_id"]}
    [root] = [s for s in tr["spans"] if s["parent_id"] is None]
    assert root["name"] == "serving.request"
    assert all(s["parent_id"] == root["span_id"] for s in tr["spans"]
               if s["parent_id"] is not None)
    # phases land in causal order; root ends last
    order = {s["name"]: s["t_offset_s"] for s in tr["spans"]}
    assert order["QUEUE"] <= order["PREFILL"] <= order["DECODE"]
    assert root["attrs"]["outcome"] == "finished"
    assert root["attrs"]["new_tokens"] == 4


def test_trace_queue_wait_after_preemption_counts_requeue_only():
    """The re-opened QUEUE span of a preempted request is tagged with
    the wait since the preemption, not since the original submit — the
    misattribution would land exactly on the requests where 'why was
    this slow' matters most."""
    from horovod_tpu.serving.kv_pager import KVPager, PagedKVCache
    from horovod_tpu.serving.scheduler import Request, Scheduler

    now = [0.0]
    pager = KVPager(PagedKVCache(n_layers=1, num_blocks=16, block_size=4,
                                 rows=((1, 4), (1, 4))))
    s = Scheduler(pager, max_active=2, prefill_token_budget=1000,
                  clock=lambda: now[0])
    old_rate = trace.TRACER.sample_rate
    trace.TRACER.sample_rate = 1.0
    try:
        req = Request(req_id=0, prompt=np.arange(4, dtype=np.int32),
                      max_new_tokens=8)
        req.trace = trace.TRACER.start_trace("req", lane="req0")
        s.submit(req)
        now[0] = 2.0
        assert s.admit() == [req]
        now[0] = 10.0
        req.generated = [1, 2]
        req.context_len = 6
        s.preempt(req)
        now[0] = 11.0
        assert s.admit() == [req]
        s.finish(req)
    finally:
        trace.TRACER.sample_rate = old_rate
    spans = trace.TRACER.export(req.trace.trace_id)["spans"]
    waits = [sp["attrs"]["queue_wait_s"] for sp in spans
             if sp["name"] == "QUEUE"]
    assert waits == [pytest.approx(2.0), pytest.approx(1.0)], waits


# ---------------------------------------------------------------------------
# SLO engine (obs/slo)
# ---------------------------------------------------------------------------

def test_slo_parse_spec_forms_and_errors():
    s = slo.parse_spec("p99(ttft) < 250ms over 5m")
    assert s.metric == "hvd_serving_ttft_seconds"
    assert s.quantile == 0.99
    assert s.threshold_s == pytest.approx(0.25)
    assert s.window_s == 300.0
    assert s.objective == 0.99 and s.budget == pytest.approx(0.01)
    s = slo.parse_spec("p95(itl)<=50ms", name="itl")
    assert s.name == "itl" and s.window_s == 300.0  # default 5m
    s = slo.parse_spec("p50(my_hist_seconds) < 2s over 1h")
    assert s.metric == "my_hist_seconds" and s.window_s == 3600.0
    s = slo.parse_spec("p99.9(queue_wait) < 1s over 30s")
    assert s.quantile == pytest.approx(0.999)
    specs = slo.parse_spec_list(
        "a=p99(ttft) < 250ms over 5m; p95(itl) < 50ms;")
    assert [x.name for x in specs] == ["a", "itl_p95"]
    for bad in ("p99(ttft)", "ttft < 250ms", "p0(ttft) < 1s",
                "p100(ttft) < 1s", "p99(ttft) < 0ms",
                "p99(ttft) < 1parsec"):
        with pytest.raises(slo.SLOError):
            slo.parse_spec(bad)


def test_slo_good_fraction_and_quantile_hand_built():
    edges = (0.1, 0.25, 1.0)
    # 6 obs <= 0.1, 2 in (0.1, 0.25], 1 in (0.25, 1.0], 1 overflow
    cum = [6, 8, 9, 10]
    assert slo.good_fraction(edges, cum, 0.25) == pytest.approx(0.8)
    assert slo.good_fraction(edges, cum, 0.1) == pytest.approx(0.6)
    # interpolation inside (0.1, 0.25]: halfway -> 6 + 2*(0.075/0.15)
    assert slo.good_fraction(edges, cum, 0.175) == pytest.approx(0.7)
    # below the first edge: linear from zero
    assert slo.good_fraction(edges, cum, 0.05) == pytest.approx(0.3)
    # past the last finite edge: overflow obs stay bad (conservative)
    assert slo.good_fraction(edges, cum, 5.0) == pytest.approx(0.9)
    assert slo.good_fraction(edges, [0, 0, 0, 0], 0.1) == 1.0  # no traffic
    # quantiles: same interpolation convention
    assert slo.quantile(edges, cum, 0.6) == pytest.approx(0.1)
    assert slo.quantile(edges, cum, 0.7) == pytest.approx(0.175)
    assert slo.quantile(edges, cum, 0.99) == 1.0   # lands in +Inf: clamp
    assert slo.quantile(edges, [0, 0, 0, 0], 0.5) is None
    assert slo.attainment_of([0.1, 0.2, 0.9], 0.25) == pytest.approx(2 / 3)
    assert slo.attainment_of([], 0.25) == 1.0


def test_slo_engine_burn_rates_windows_and_violations():
    reg = MetricRegistry()
    h = reg.histogram("lat_seconds", buckets=(1.0, 2.0))
    now = [0.0]
    eng = slo.SLOEngine(registry=reg, clock=lambda: now[0], tick_s=1.0,
                        burn_windows=(("fast", 60.0), ("slow", 600.0)))
    eng.add("p90(lat_seconds) < 1s over 60s", name="lat")
    eng.tick()                              # zero baseline at t=0
    for _ in range(18):
        h.observe(0.5)                      # good
    for _ in range(2):
        h.observe(1.5)                      # bad
    now[0] = 30.0
    eng.tick()
    out = eng.evaluate()["lat"]
    # 18/20 good = exactly the 0.9 objective: met, burning the whole
    # budget (burn 1.0) but not over it.
    assert out["attainment"] == pytest.approx(0.9)
    assert out["met"] is True
    assert out["burn_rate"]["fast"] == pytest.approx(1.0)
    v = eng._c_violations.labels(slo="lat")
    assert v.value == 0
    for _ in range(10):
        h.observe(1.5)                      # 12 bad / 30 total
    now[0] = 60.0
    eng.tick()
    out = eng.evaluate()["lat"]
    assert out["attainment"] == pytest.approx(0.6)
    assert out["met"] is False
    assert out["burn_rate"]["fast"] == pytest.approx(4.0)  # 0.4 / 0.1
    assert v.value == 1                    # met -> violated transition
    eng.evaluate()
    assert v.value == 1                    # still violated: no re-count
    # traffic stops; the fast window slides past the bad burst and the
    # SLO recovers (empty window = attainment 1.0), re-arming the edge.
    now[0] = 150.0
    eng.tick()
    out = eng.evaluate()["lat"]
    assert out["attainment"] == 1.0 and out["met"] is True
    assert out["burn_rate"]["fast"] == 0.0
    # gauges landed in the registry (the /metrics + /cluster surface)
    text = export.to_prometheus(reg.snapshot())
    assert 'hvd_slo_attainment{slo="lat"} 1' in text
    assert 'hvd_slo_burn_rate{slo="lat",window="fast"} 0' in text
    assert 'hvd_slo_objective{slo="lat"} 0.9' in text
    assert 'hvd_slo_violations_total{slo="lat"} 1' in text


def test_slo_cum_counts_reads_registry_histograms():
    reg = MetricRegistry()
    h = reg.histogram("cc_seconds", buckets=(0.1, 1.0), labelnames=("k",))
    h.labels(k="a").observe(0.05)
    h.labels(k="b").observe(0.5)
    h.labels(k="b").observe(5.0)
    edges, cum = slo.cum_counts("cc_seconds", reg)
    assert edges == (0.1, 1.0)
    assert cum == [1, 2, 3]                 # children summed, +Inf last
    assert slo.cum_counts("missing", reg) == (None, None)
    reg.counter("not_hist_total").inc()
    assert slo.cum_counts("not_hist_total", reg) == (None, None)


def test_slo_engine_history_stays_bounded():
    reg = MetricRegistry()
    h = reg.histogram("lat_seconds", buckets=(1.0,))
    now = [0.0]
    eng = slo.SLOEngine(registry=reg, clock=lambda: now[0], tick_s=10.0,
                        burn_windows=(("fast", 60.0), ("slow", 600.0)))
    eng.add("p90(lat_seconds) < 1s over 60s", name="lat")
    for i in range(1000):
        h.observe(0.5)
        now[0] = float(i * 10)
        eng.tick()
    snaps = eng._hist["lat_seconds"].snaps
    # horizon = max(window) + 2 ticks = 620s -> ~63 snapshots at 10s
    assert len(snaps) <= 640 / 10 + 3
    assert eng.evaluate()["lat"]["met"] is True


def test_slo_arm_status_disarm_roundtrip():
    eng = slo.arm("rt=p99(ttft) < 250ms over 5m", tick_s=3600)
    try:
        assert eng is not None
        st = slo.status()
        assert st["rt"]["objective"] == 0.99
        assert set(st["rt"]["burn_rate"]) == {"5m", "1h"}
    finally:
        slo.disarm()
    assert slo.status() == {}
    assert slo.arm("   ") is None          # empty spec list: unarmed


# ---------------------------------------------------------------------------
# flight recorder (obs/flightrec)
# ---------------------------------------------------------------------------

def test_flightrec_ring_is_bounded_and_ordered():
    rec = flightrec.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("tick", name=f"e{i}", i=i)
    assert len(rec) == 8
    snap = rec.snapshot()
    assert [e["name"] for e in snap] == [f"e{i}" for i in range(12, 20)]
    assert all(e["kind"] == "tick" and e["data"]["i"] >= 12 for e in snap)
    assert [e["t_mono_s"] for e in snap] == \
        sorted(e["t_mono_s"] for e in snap)


def test_flightrec_concurrent_appends_stay_bounded():
    rec = flightrec.FlightRecorder(capacity=128)
    n_threads, per_thread = 8, 2000
    before = REGISTRY.get("hvd_flightrec_events_total").total()

    def work(t):
        for i in range(per_thread):
            rec.record("t", name=f"{t}.{i}")

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec) == 128
    assert len(rec.snapshot()) == 128
    assert REGISTRY.get("hvd_flightrec_events_total").total() - before \
        == n_threads * per_thread


def test_flightrec_capacity_resize_and_disable():
    rec = flightrec.FlightRecorder(capacity=8)
    for i in range(8):
        rec.record("e", name=str(i))
    rec.set_capacity(4)                    # shrink keeps the newest
    assert [e["name"] for e in rec.snapshot()] == ["4", "5", "6", "7"]
    rec.set_capacity(16)                   # grow keeps everything held
    assert len(rec) == 4
    rec.set_capacity(0)                    # disable: record is a no-op
    rec.record("e", name="x")
    assert len(rec) == 0 and rec.snapshot() == []


def test_flightrec_dump_bundle_contents(tmp_path):
    class FakeStall:
        missing_ranks = (3, 1)
        age_ms = 2500

    rec = flightrec.FlightRecorder(capacity=16)
    rec.set_identity(0, 4)
    rec.record("stall_warning", desc="t.x")
    path = rec.dump(str(tmp_path / "b.json"), reason="stall_shutdown",
                    stall={"t.x": FakeStall()},
                    extra={"error": "stalled"})
    assert path == str(tmp_path / "b.json")
    bundle = json.loads((tmp_path / "b.json").read_text())
    assert bundle["reason"] == "stall_shutdown"
    assert bundle["rank"] == 0 and bundle["size"] == 4
    assert bundle["events"][0]["kind"] == "stall_warning"
    assert bundle["stall"]["t.x"]["missing_ranks"] == [1, 3]   # sorted
    assert bundle["stall"]["t.x"]["missing_rank_bitmap"] == 0b1010
    assert bundle["stall"]["t.x"]["age_ms"] == 2500
    assert bundle["extra"]["error"] == "stalled"
    assert any(f["name"] == "hvd_flightrec_events_total"
               for f in bundle["metrics"])
    assert not list(tmp_path.glob("*.tmp.*"))   # atomic: no torn files


def test_flightrec_maybe_dump_only_when_armed(tmp_path):
    rec = flightrec.FlightRecorder(capacity=4)
    rec.record("e", name="x")
    assert rec.maybe_dump("round_abort") is None     # unarmed: no file
    rec.arm(str(tmp_path))
    path = rec.maybe_dump("round_abort")
    assert path is not None and os.path.dirname(path) == str(tmp_path)
    assert "round_abort" in os.path.basename(path)
    json.loads(open(path).read())
    rec.arm(None)                                    # disarm again
    assert rec.maybe_dump("round_abort") is None


def test_hvd_flight_record_manual_api(tmp_path):
    path = hvd.flight_record(str(tmp_path / "manual.json"))
    assert path == str(tmp_path / "manual.json")
    bundle = json.loads((tmp_path / "manual.json").read_text())
    assert bundle["reason"] == "manual"
    # the session engine's traffic is visible in the bundle's registry
    assert any(f["name"] == "hvd_collectives_total"
               for f in bundle["metrics"])


# ---------------------------------------------------------------------------
# /healthz + stale-rank aggregation
# ---------------------------------------------------------------------------

def _get_healthz(port):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_healthz_ready_unready_and_provider_failure():
    saved = server._health_provider
    srv = server.MetricsServer(0, addr="127.0.0.1",
                               registry=MetricRegistry())
    try:
        server.set_health_provider(
            lambda: {"ready": True, "status": "ok", "rank": 0, "size": 2})
        code, body = _get_healthz(srv.port)
        assert code == 200 and body["ready"] is True and body["size"] == 2
        server.set_health_provider(lambda: {"ready": False,
                                            "status": "unready"})
        code, body = _get_healthz(srv.port)
        assert code == 503 and body["ready"] is False
        # no provider = the shutdown->init window of an elastic
        # re-rendezvous: answer 503, never 500/404
        server.set_health_provider(None)
        code, body = _get_healthz(srv.port)
        assert code == 503 and "re-rendezvous" in body["reason"]
        # a crashing provider must still answer the probe
        def boom():
            raise RuntimeError("broken provider")
        server.set_health_provider(boom)
        code, body = _get_healthz(srv.port)
        assert code == 503 and "broken provider" in body["reason"]
    finally:
        server.set_health_provider(saved)
        srv.close()


def test_healthz_live_session_is_ready():
    """The conftest session ran hvd.init(): the armed provider reports
    this rank ready with a fresh negotiation age."""
    srv = server.MetricsServer(0, addr="127.0.0.1")
    try:
        code, body = _get_healthz(srv.port)
    finally:
        srv.close()
    assert code == 200, body
    assert body["ready"] is True and body["engine_alive"] is True
    assert body["rank"] == 0 and body["size"] >= 1
    assert body["uptime_s"] > 0
    assert body["last_negotiation_age_s"] >= 0.0


def test_merge_marks_stale_rank_and_excludes_it_from_sums():
    """A rank whose snapshot outlived 2x its publish interval is flagged
    stale, dropped from summed/merged cluster series and from
    ranks_reporting — a dead rank must not mask live stragglers."""
    import time as _time
    snaps = []
    for r in range(2):
        reg = MetricRegistry()
        reg.counter("st_events_total").inc(r + 1)
        reg.histogram("st_lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
        snap = json.loads(aggregate.local_snapshot_blob(
            r, 2, registry=reg,
            extra_meta={"interval_s": 2.0}).decode())
        snaps.append(snap)
    snaps[1]["time"] = _time.time() - 100.0      # rank 1 stopped publishing
    merged = aggregate.merge_snapshots(snaps)
    text = export.to_prometheus(merged)
    export.validate_prometheus(text)
    # per-rank series survive as postmortem signal...
    assert 'st_events_total{rank="0"} 1' in text
    assert 'st_events_total{rank="1"} 2' in text
    # ...but the cluster sum and bucket merge cover live ranks only
    assert "\nst_events_total 1\n" in "\n" + text
    assert "st_lat_seconds_count 1" in text
    assert "horovod_tpu_cluster_ranks_reporting 1" in text
    assert "horovod_tpu_cluster_ranks_stale 1" in text
    assert ('horovod_tpu_rank_snapshot_age_seconds'
            '{rank="0",stale="false"}') in text
    assert ('horovod_tpu_rank_snapshot_age_seconds'
            '{rank="1",stale="true"}') in text
    # both fresh: everything sums, nothing stale
    snaps[1]["time"] = _time.time()
    text = export.to_prometheus(aggregate.merge_snapshots(snaps))
    assert "\nst_events_total 3\n" in "\n" + text
    assert "horovod_tpu_cluster_ranks_reporting 2" in text
    assert "horovod_tpu_cluster_ranks_stale 0" in text


@pytest.mark.integration
@pytest.mark.slow
def test_flightrec_dump_on_np2_stall(tmp_path):
    """Acceptance: an induced np=2 stall auto-dumps a postmortem bundle
    whose attribution names the withholding rank (list + bitmap).
    slow-marked: the bundle/attribution logic is unit-tested above and
    the stall plumbing is covered by the np=4 straggler e2e; this job
    exists to prove the end-to-end auto-dump and costs two runner
    startups plus the full stall-shutdown wait."""
    res = _hvdrun(2, extra_env={
        "HVDTPU_TEST_MODE": "flightrec",
        "HVDTPU_FLIGHT_RECORDER_DIR": str(tmp_path),
        "HVDTPU_STALL_CHECK_TIME_SECONDS": "2",
        "HVDTPU_STALL_SHUTDOWN_TIME_SECONDS": "4",
    })
    assert res.returncode == 0, res.stdout + res.stderr
    assert "rank 0: FLIGHTREC-OK" in res.stdout, res.stdout
    assert "rank 1: FLIGHTREC-BYSTANDER-OK" in res.stdout, res.stdout
    assert list(tmp_path.glob("flightrec-rank0-*-stall_shutdown-*.json"))


def test_serving_request_metrics_reach_registry():
    import jax

    from horovod_tpu import serving
    from horovod_tpu.models import llama

    ttft = REGISTRY.get("hvd_serving_ttft_seconds")
    reqs = REGISTRY.get("hvd_serving_requests_total")
    before_count = ttft.count
    before_done = reqs.labels(outcome="finished").value

    cfg = llama.LlamaConfig.tiny()            # v256 d64 L2 H4 KV2 fp32
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sess = serving.serve(params, cfg, num_blocks=16, block_size=8,
                         max_active=2)
    fut = sess.submit(np.arange(5, dtype=np.int32), max_tokens=4)
    sess.drain()
    res = fut.result(timeout=30)
    assert len(res.tokens) == 4
    assert ttft.count == before_count + 1
    assert reqs.labels(outcome="finished").value == before_done + 1
    assert REGISTRY.get("hvd_serving_kv_utilization") is not None
    text = hvd.metrics("prometheus")
    assert "hvd_serving_ttft_seconds_bucket" in text
    assert "hvd_serving_kv_utilization" in text
