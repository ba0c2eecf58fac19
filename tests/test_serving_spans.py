"""The serving loop names itself: ``hvd.serve.*`` profiler spans with the
counts taken where the work happens, jitted steps and Pallas kernels that
say what they are, and the per-tick table counters.

CPU: a tiny engine under ``jax.profiler.trace``, read back with
``jax.profiler.ProfileData``, through the XLA gather and through the
paged kernel in the Pallas interpreter.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import llama, steps
from horovod_tpu.obs import REGISTRY
from horovod_tpu.obs import trace as obs_trace
from horovod_tpu.ops import flash_attention as FA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: span -> its parent; every span of ISSUE 27's table
PARENT = {
    "hvd.serve.step": None,
    "hvd.serve.admit": "hvd.serve.step",
    "hvd.serve.prefill": "hvd.serve.step",
    "hvd.serve.prefill.dispatch": "hvd.serve.prefill",
    "hvd.serve.prefill.fetch": "hvd.serve.prefill",
    "hvd.serve.decode": "hvd.serve.step",
    "hvd.serve.decode.grow": "hvd.serve.decode",
    "hvd.serve.decode.tables": "hvd.serve.decode",
    "hvd.serve.decode.dispatch": "hvd.serve.decode",
    "hvd.serve.decode.fetch": "hvd.serve.decode",
    "hvd.serve.decode.emit": "hvd.serve.decode",
    "hvd.serve.deliver": None,
}
#: a turn's last prefill holds its tick, dispatched before the prefill's
#: token is fetched; the turn after the last tick fetches it with no tick
#: of its own
ALSO_UNDER = {"hvd.serve.decode": "hvd.serve.prefill",
              "hvd.serve.decode.fetch": "hvd.serve.step",
              "hvd.serve.decode.emit": "hvd.serve.step"}
COUNTERS = ("hvd_serving_decode_table_slots_total",
            "hvd_serving_decode_table_blocks_total",
            "hvd_serving_decode_idle_rows_total")
#: every attribute a span may carry: each has a reader (PERF.md, section 3)
DEPTH = {"loops", "cache_layers"}            # the model's, on both (PR 33)
ATTRS = {"hvd.serve.step": {"step"},
         "hvd.serve.prefill": {"req", "tokens", "cached", "resumed"} | DEPTH,
         "hvd.serve.decode": {"n_cols", "blocks", "rows", "blocks_held",
                              "blocks_usable", "kv_tokens", "ahead"} | DEPTH}


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()            # v256 d64 L2 H4 KV2 fp32
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _session(tiny, use_flash, **kw):
    cfg, params = tiny
    return serving.serve(params, cfg, block_size=4, num_blocks=64,
                         max_active=4, use_flash=use_flash,
                         prefill_buckets=(8, 16, 32), **kw)


def _hvd_spans(trace_dir):
    """(name, start_ns, end_ns, attrs) of the trace's hvd.* host events."""
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in data.planes for line in plane.lines
        for e in line.events if e.name.startswith("hvd."))


@pytest.mark.parametrize("use_flash", ["never", "interpret"])
def test_spans_attributes_and_counters_of_a_traced_run(tiny, use_flash,
                                                       tmp_path):
    session = _session(tiny, use_flash)
    engine = session.engine
    rng = np.random.RandomState(3)
    submit = lambda n: session.submit(
        rng.randint(0, 256, size=(n,)).astype(np.int32), 5)
    for n in (5, 9, 13):                      # warm every shape
        submit(n)
    session.drain()

    # what each tick's table really held, taken beside the engine
    real_tables = engine.pager.table_matrix
    ticks = []

    def table_matrix(ids, n_cols):
        usable = engine.cache.num_blocks - 1
        ticks.append(dict(
            blocks=sum(len(engine.pager.table(i)) for i in ids if i >= 0),
            rows=sum(i >= 0 for i in ids),
            n_cols=n_cols, blocks_held=usable - engine.pager.free_blocks,
            blocks_usable=usable, loops=1, cache_layers=2,
            kv_tokens=sum(r.context_len + 1 for r in engine._slots
                          if r is not None)))
        return real_tables(ids, n_cols)
    engine.pager.table_matrix = table_matrix

    before = [REGISTRY.get(c).value for c in COUNTERS]
    ahead_before = REGISTRY.get("hvd_serving_decode_ahead_total").value
    lens = (5, 9, 13, 7, 11, 6)               # 6 requests, 4 slots
    with jax.profiler.trace(str(tmp_path)):
        futs = [submit(n) for n in lens]
        session.drain()
    assert all(len(f.result(timeout=0).tokens) == 5 for f in futs)
    session.close()
    spans = _hvd_spans(tmp_path)
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)

    assert set(by_name) == set(PARENT)
    assert all(set(s[3]) == ATTRS.get(s[0], set()) for s in spans)
    for name, parent in PARENT.items():
        parents = [parent] + ([ALSO_UNDER[name]] if name in ALSO_UNDER
                              else [])
        for _, t0, t1, _ in by_name[name]:
            if parent is not None:
                assert any(p0 <= t0 and t1 <= p1 for p in parents
                           for _, p0, p1, _ in by_name[p]), name
    # a turn is one step and one deliver, and the deliver comes after it
    steps, delivers = by_name["hvd.serve.step"], by_name["hvd.serve.deliver"]
    assert len(steps) == len(delivers)
    assert all(s[2] <= d[1] for s, d in zip(steps, delivers))
    assert [s[3]["step"] for s in steps] == list(
        range(steps[0][3]["step"], steps[0][3]["step"] + len(steps)))

    prefills = by_name["hvd.serve.prefill"]
    assert sum(p[3]["tokens"] for p in prefills) == sum(lens)
    assert all(p[3]["cached"] == 0 and p[3]["resumed"] == 0
               and p[3]["cache_layers"] == 2 for p in prefills)
    assert len({p[3]["req"] for p in prefills}) == len(lens)

    decodes = by_name["hvd.serve.decode"]
    assert len(decodes) == len(ticks) > 0
    # every tick but the first is dispatched with the one before in flight
    assert [d[3].pop("ahead") for d in decodes] == [0] + [1] * (
        len(decodes) - 1)
    assert REGISTRY.get("hvd_serving_decode_ahead_total").value \
        - ahead_before == len(decodes) - 1
    assert [d[3] for d in decodes] == ticks

    after = [REGISTRY.get(c).value for c in COUNTERS]
    assert [b - a for a, b in zip(before, after)] == [
        sum(engine.ecfg.max_active * d[3]["n_cols"] for d in decodes),
        sum(d[3]["blocks"] for d in decodes),
        sum(engine.ecfg.max_active - d[3]["rows"] for d in decodes)]
    # 6 requests over 4 slots: the tail of the run leaves slots empty
    rows = {d[3]["rows"] for d in decodes}
    assert max(rows) == 4 and 0 < min(rows) < 4


def test_prefix_hit_and_speculative_rounds_carry_the_same_spans(tiny,
                                                                tmp_path):
    """The cached prefill reports what it skipped; a speculative round is
    a hvd.serve.decode span with the table's counts."""
    cfg, params = tiny
    session = _session(tiny, "never", prefix_cache=True, spec_k=2,
                       draft_params=params, draft_cfg=cfg)
    head = np.arange(1, 13, dtype=np.int32)
    with jax.profiler.trace(str(tmp_path)):
        for tail in (20, 30):
            session.submit(np.concatenate(
                [head, np.asarray([tail, tail + 1], np.int32)]), 4)
            session.drain()
    session.close()
    spans = _hvd_spans(tmp_path)
    cached = [s[3]["cached"] for s in spans if s[0] == "hvd.serve.prefill"]
    assert cached[0] == 0 and cached[1] >= 8
    rounds = [s[3] for s in spans if s[0] == "hvd.serve.decode"]
    assert rounds and all(r["blocks"] > 0 and r["n_cols"] > 0
                          for r in rounds)


def test_expert_layers_counts_reach_the_spans_and_the_metrics(tmp_path):
    """A model with expert layers: the decode span says how many (layer,
    expert) pairs the tick touched of those held and how many (row,
    expert) pairs it ran, the prefill span its pairs, and the per-layer
    routing metrics advance by the same counts; a dense model's spans
    carry none of the three."""
    from horovod_tpu.models import glm_moe_lite as G
    cfg = G.GlmMoeLiteConfig.tiny()          # 1 dense + 2 expert layers
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    session = serving.serve(params, cfg, block_size=4, num_blocks=64,
                            max_active=4, use_flash="interpret",
                            prefill_buckets=(8, 16))
    rng = np.random.RandomState(5)
    submit = lambda n: session.submit(
        rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32), 4)
    submit(9)
    session.drain()                           # warm
    held = REGISTRY.get("hvd_moe_held_pairs_total")
    before = [held.labels(layer=l).value for l in ("2", "3")]
    combine = REGISTRY.get("hvd_moe_combine_steps_total")
    steps_before = [combine.labels(layer="3", form=f).value
                    for f in ("list", "add", "kernel")]
    with jax.profiler.trace(str(tmp_path)):
        for n in (5, 9, 13):
            submit(n)
        session.drain()
    session.close()
    spans = _hvd_spans(tmp_path)
    prefills = [s[3] for s in spans if s[0] == "hvd.serve.prefill"]
    decodes = [s[3] for s in spans if s[0] == "hvd.serve.decode"]
    # a tick's counts come with its tokens, onto the span of the turn
    # that fetches them: the next tick's, or the last turn's own
    fetched = [s[3] for s in spans if "experts_touched" in s[3]]
    assert set(prefills[0]) == ATTRS["hvd.serve.prefill"] | {
        "moe_pairs", "moe_combine"}
    assert set(decodes[0]) == ATTRS["hvd.serve.decode"]
    assert set(decodes[1]) == ATTRS["hvd.serve.decode"] | {
        "moe_pairs", "moe_combine", "experts_touched", "experts_held"}
    assert len(fetched) == len(decodes) and fetched[:-1] == decodes[1:]
    # every scored expert is held, so a prefill's results come back
    # through the list, and a tick's, under the engine's kernels, through
    # hvd_moe_decode; the steps are counted under those forms alone
    assert {s["moe_combine"] for s in prefills} == {"list"}
    assert {s["moe_combine"] for s in fetched} == {"kernel"}
    steps = [combine.labels(layer="3", form=f).value - b
             for f, b in zip(("list", "add", "kernel"), steps_before)]
    assert steps == [len(prefills), 0, len(decodes)]
    # a prefill routes every row of its bucket, a tick all four slots'
    assert [p["moe_pairs"] for p in prefills] == [
        2 * 2 * b for b in (8, 16, 16)]
    assert all(d["moe_pairs"] == 2 * 2 * 4 and d["experts_held"] == 2 * 8
               and 2 <= d["experts_touched"] <= 16 for d in fetched)
    assert all(d["kv_tokens"] >= d["rows"] for d in decodes)
    pairs = sum(s["moe_pairs"] for s in prefills + fetched)
    after = [held.labels(layer=l).value for l in ("2", "3")]
    assert [b - a for a, b in zip(before, after)] == [pairs / 2] * 2
    load = REGISTRY.get("hvd_moe_expert_load_max_over_mean")
    assert load.labels(layer="3").value >= 1.0
    assert session.engine.attention_path == "pallas-mla-interpret"


def test_jitted_steps_are_named(tiny):
    engine = _session(tiny, "never").engine
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    kv = jax.ShapeDtypeStruct((2, 1, 8, 2, 16), jnp.float32)
    pools = engine.pools
    lowered = {
        "prefill": engine._prefill.lower(engine.params, i32(1, 8), i32(1)),
        "scatter": engine._scatter.lower(pools, (kv, kv), i32(2)),
        "decode": engine.lower_decode(2),
        "extend": engine._extend.lower(
            engine.params, pools, i32(1, 4), i32(1, 4),
            jax.ShapeDtypeStruct((1, 4), jnp.bool_), i32(1, 2)),
    }
    for what, low in lowered.items():
        assert f"jit_hvd_serve_{what}" in low.as_text()[:200], what


def test_scatter_cuts_the_bucket_to_the_blocks_itself(tiny):
    """A prefill bucket longer than the request's blocks goes to the
    scatter whole: no eager slice (a program of its own) on the way."""
    engine = _session(tiny, "never").engine
    L, KV, Dh = 2, 2, 16
    ks = jnp.arange(L * 8 * KV * Dh, dtype=jnp.float32).reshape(
        L, 1, 8, KV, Dh)
    blocks = jnp.asarray([3], jnp.int32)       # one block of 4 positions
    kp, vp = engine._scatter(engine.pools, (ks, -ks), blocks)
    np.testing.assert_array_equal(kp[:, 3], ks[:, 0, :4])
    np.testing.assert_array_equal(vp[:, 3], -ks[:, 0, :4])
    assert not np.asarray(kp[:, 4]).any()


def test_pallas_kernels_are_named():
    q = jnp.zeros((1, 256, 2, 128), jnp.float32)
    loss = lambda q, k, v: FA.flash_attention(
        q, k, v, block_q=128, block_k=128, interpret=True).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert name in text, name
    pool = jnp.zeros((2, 8, 4, 2, 16), jnp.float32)
    text = str(jax.make_jaxpr(lambda *a: FA.paged_attention(
        *a, interpret=True))(
            jnp.zeros((2, 4, 16)), pool, pool, jnp.int32(1),
            jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32)))
    assert "hvd_paged_decode" in text


def _v5e_spec(monkeypatch):
    """``spec(shape, dtype)``: a ShapeDtypeStruct on one chip of a
    compile-only v5e:2x2 (no chip needed); skips without libtpu."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"no compile-only TPU topology: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)


def _mosaic_lines(compiled):
    return [ln.split(" = ")[0].strip()
            for ln in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def test_kernel_names_reach_the_compiled_tpu_module(monkeypatch):
    """Compiled for a v5e (no chip needed), each Mosaic custom call is an
    instruction whose own name holds the kernel's (autodiff wraps it:
    %transpose_jvp_hvd_flash_bwd_dq__.1): the left side of the line that a
    device trace shows the op by."""
    spec = _v5e_spec(monkeypatch)

    def mosaic_lines(fn, *args):
        return _mosaic_lines(jax.jit(fn).lower(*args).compile())

    qkv = spec((1, 512, 4, 128))
    grads = jax.grad(lambda q, k, v: FA.flash_attention(q, k, v).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))
    names = mosaic_lines(grads, qkv, qkv, qkv)
    assert len(names) == 3
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert sum(kernel in n for n in names) == 1, (kernel, names)
    pool = spec((2, 64, 16, 8, 128))
    names = mosaic_lines(
        FA.paged_attention, spec((4, 32, 128)), pool, pool,
        spec((), jnp.int32), spec((4, 8), jnp.int32), spec((4,), jnp.int32))
    assert len(names) == 1 and "hvd_paged_decode" in names[0], names


@pytest.mark.parametrize("n_cols", [8, 256])
@pytest.mark.parametrize("KV,H", [(8, 32), (16, 16)],
                         ids=["mistral-kv8-h32", "ouro-kv16-h16"])
def test_paged_kernel_compiles_at_both_served_geometries(monkeypatch, KV, H,
                                                         n_cols):
    """The kernel that copies live pages only (PR 34: run-time page
    counts, waits by their binary digits, a scalar search for the next
    row with a stream), compiled for a v5e at the two serving cells'
    geometries, 32 rows of 16-token pages, the narrowest and the widest
    table: one Mosaic call, named ``hvd_paged_decode``, and no
    temporary near a layer's pages, so the pool is not copied."""
    spec = _v5e_spec(monkeypatch)
    L, NB, BS, R = 2, 512, 16, 32
    pool = spec((L, NB, BS, KV, 128))
    compiled = jax.jit(FA.paged_attention).lower(
        spec((R, H, 128)), pool, pool, spec((), jnp.int32),
        spec((R, n_cols), jnp.int32), spec((R,), jnp.int32)).compile()
    names = _mosaic_lines(compiled)
    assert len(names) == 1 and "hvd_paged_decode" in names[0], names
    layer_bytes = NB * BS * KV * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes // 8


def test_decode_step_compiles_to_one_paged_kernel_and_no_pool_copy(
        monkeypatch):
    """The decode tick at the backlog cell's configuration (Mistral 7B
    widths, 16 layers, 32 slots, a 4 GiB pool of 16-token pages, the
    widest table), compiled for a v5e: one Mosaic call, named
    ``hvd_paged_decode`` (what the benchmark's reducers find the decode
    program and the kernel by), the donated pools updated in place, and
    temporaries far under one layer's pages (128 MiB), so neither pool
    nor a layer of one is copied for the kernel."""
    from horovod_tpu.models import llama
    spec = _v5e_spec(monkeypatch)
    L, NB, BS, R, n_cols = 16, 4096, 16, 32, 256
    cfg = llama.LlamaConfig(
        vocab_size=32768, d_model=4096, n_layers=L, n_heads=32,
        n_kv_heads=8, d_ff=14336, rope_theta=1e6, dtype=jnp.bfloat16)
    assert llama.paged_kernel_ok(cfg, None, BS)
    params = jax.tree.map(
        lambda s: spec(s.shape, s.dtype), jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    pool = spec((L, NB, BS, cfg.n_kv_heads, cfg.head_dim))
    i32 = lambda *shape: spec(shape, jnp.int32)
    step = jax.jit(
        lambda p, tok, pos, kp, vp, tables: steps.decode_step_paged(
            p, tok, pos, (kp, vp), tables, cfg, use_flash=True),
        donate_argnums=(3, 4))
    compiled = step.lower(params, i32(R), i32(R), pool, pool,
                          i32(R, n_cols)).compile()
    names = _mosaic_lines(compiled)
    assert len(names) == 1 and "hvd_paged_decode" in names[0], names
    mem = compiled.memory_analysis()
    pool_bytes = L * NB * BS * cfg.n_kv_heads * cfg.head_dim * 2
    assert mem.alias_size_in_bytes == 2 * pool_bytes
    assert mem.temp_size_in_bytes < (pool_bytes // L) // 8, mem


def test_profiler_span_is_a_noop_context_without_the_profiler():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs_trace.profiler_span("hvd.test.nothing", n=1) as sp:
        sp.set_metadata(m=2)
    # the shared no-op that stands in before jax is imported
    with obs_trace.NULL_SPAN as sp:
        sp.set_metadata(m=2)


def test_obs_trace_imports_without_jax():
    """``obs`` alone (the package's ``__init__`` pulls jax in through
    ``ops``): ``obs.trace`` loads, and spans, without jax."""
    code = textwrap.dedent(f"""
        import sys, types
        pkg = types.ModuleType("horovod_tpu")
        pkg.__path__ = [{os.path.join(ROOT, "horovod_tpu")!r}]
        sys.modules["horovod_tpu"] = pkg
        from horovod_tpu.obs import trace
        with trace.profiler_span("hvd.test.nothing", n=1) as sp:
            sp.set_metadata(m=2)
        assert trace.profiler_span("hvd.test.nothing") is trace.NULL_SPAN
        assert "jax" not in sys.modules, "obs.trace imported jax"
    """)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
