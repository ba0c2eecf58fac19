"""Global runtime context: the TPU-native equivalent of the reference's
``HorovodGlobalState`` + ``Controller`` rank bookkeeping.

Reference semantics († ``horovod/common/operations.cc`` ``horovod_init`` /
``horovod_rank`` / ``horovod_size``; † ``horovod/common/basics.py``):
every *process* is one rank, owning exactly one accelerator, and collectives
run across processes.

TPU-native mapping: JAX is a single-controller-per-host SPMD system where one
process drives several chips, so the *collective participant* is the device,
not the process:

- ``size()``        = number of devices in the global mesh (all hosts)
- ``rank()``        = global index of this process's first addressable device
- ``local_size()``  = number of devices this process drives
- ``local_rank()``  = index of the process among processes on this host (0 in
                      single-host mode), matching the reference's use of
                      local_rank for GPU pinning — on TPU, device pinning is
                      automatic, so this is informational
- ``cross_rank()``  = process index (host index across the job)
- ``cross_size()``  = process count

The 8-fake-device CPU rig (``--xla_force_host_platform_device_count=8``) then
behaves like ``horovodrun -np 8`` for testing: 8 participants, one process.

Multi-host: ``init()`` calls ``jax.distributed.initialize`` when a coordinator
address is configured (env ``HVDTPU_COORDINATOR_ADDR`` or args), after which
``jax.devices()`` spans all hosts and the same code paths work unchanged —
XLA's ICI/DCN collectives replace the reference's NCCL/MPI split.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from . import config as config_mod
from .utils import logging as hvd_logging

log = hvd_logging.get_logger()


class NotInitializedError(RuntimeError):
    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; call horovod_tpu.init() "
            "first (reference parity: hvd.init())")


class _GlobalState:
    """Singleton runtime state († ``global_state.h HorovodGlobalState``)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.initialized = False
        self.config: config_mod.Config = config_mod.Config()
        self.devices: Sequence[jax.Device] = ()
        self.mesh: Optional[Mesh] = None          # flat 1-D mesh, axis = dp_axis
        self.engine = None                        # ops.engine.CollectiveEngine
        self.timeline = None                      # utils.timeline.Timeline
        self.process_set_table = None             # ops.process_sets table

    # -- rank bookkeeping ---------------------------------------------------
    @property
    def size(self) -> int:
        return len(self.devices)

    @property
    def local_devices(self) -> Sequence[jax.Device]:
        return [d for d in self.devices if d.process_index == jax.process_index()]

    @property
    def rank(self) -> int:
        pidx = jax.process_index()
        for i, d in enumerate(self.devices):
            if d.process_index == pidx:
                return i
        return 0

    @property
    def local_size(self) -> int:
        return len(self.local_devices)


_state = _GlobalState()


def global_state() -> _GlobalState:
    return _state


def _skip_device_put_equality_check() -> None:
    """Multi-process only.  jax 0.9.0's ``device_put`` of a host array to
    a sharding that spans processes still runs
    ``multihost_utils.assert_equal`` (``jax/_src/dispatch.py``): a hidden
    cross-process broadcast issued from whichever thread placed the
    value, unordered against the engine's negotiated collectives, which
    can deadlock them.  Every in-repo multi-process path places identical
    host values by construction, so that one internal check — recognized
    by its ``fail_message`` — is skipped; direct user calls to
    ``assert_equal`` keep their full cross-host semantics."""
    from jax.experimental import multihost_utils as mhu
    if getattr(mhu.assert_equal, "_hvdtpu_scoped", False):
        return
    orig = mhu.assert_equal

    def scoped_assert_equal(in_tree, fail_message=""):
        if "passed to device_put" in (fail_message or ""):
            return
        return orig(in_tree, fail_message)

    scoped_assert_equal._hvdtpu_scoped = True
    mhu.assert_equal = scoped_assert_equal


def init(
    *,
    config: Optional[config_mod.Config] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    coordinator_addr: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize the runtime (reference parity: ``hvd.init()`` †3.1).

    Single-host: builds the global 1-D mesh over all (or the given) devices
    and starts the background collective engine.

    Multi-host: pass ``coordinator_addr``/``num_processes``/``process_id`` (or
    set ``HVDTPU_COORDINATOR_ADDR`` etc.); this performs the rendezvous the
    reference does via Gloo's HTTP KV store († ``gloo_context.cc
    InitializeFromEnv``), here via JAX's coordination service.
    """
    with _state.lock:
        if _state.initialized:
            log.debug("init() called twice; ignoring (reference parity)")
            return

        cfg = config_mod.from_env(config)
        hvd_logging.configure(cfg.log_level, hide_timestamp=cfg.log_hide_timestamp)
        _state.config = cfg

        if cfg.faults:
            # Strict (unlike the import-time env arming, which must not
            # crash imports): a requested fault plan with a typo must
            # fail the job, not silently run it healthy.  Re-arming an
            # identical spec on elastic re-init keeps injector state.
            from . import chaos
            chaos.arm(cfg.faults)

        if cfg.platform:
            # Must land before any backend initializes (afterwards the
            # update is a silent no-op; checked below).
            jax.config.update("jax_platforms", cfg.platform)

        from .utils.compile_cache import ensure_compile_cache
        ensure_compile_cache()

        addr = coordinator_addr or cfg.coordinator_addr
        if addr:
            jax.distributed.initialize(
                coordinator_address=addr,
                num_processes=num_processes if num_processes is not None else cfg.cross_size_env,
                process_id=process_id if process_id is not None else cfg.cross_rank_env,
            )
            _skip_device_put_equality_check()

        devs = list(devices) if devices is not None else list(jax.devices())
        if not devs:
            raise RuntimeError("no JAX devices visible")
        if cfg.platform and devices is None and \
                devs[0].platform.lower() != cfg.platform.lower():
            # jax.config.update is a silent no-op once a backend exists
            # (the script touched jax before init()) — fail fast rather
            # than start collective engines on the wrong platform.
            raise RuntimeError(
                f"requested platform={cfg.platform} but the JAX backend "
                f"already initialized as {devs[0].platform}; call "
                "hvd.init() before any other JAX use (or drop --platform)")
        if jax.process_count() > 1 and devs[0].platform == "cpu":
            # An XLA:CPU executable reloaded from the persistent cache
            # deadlocks a multi-process job's collectives (hvdrun -np 2
            # examples/llama_moe.py: the rank that hit the cache hung
            # after its first step).  TPU executables are shared between
            # processes by design and keep the cache.
            jax.config.update("jax_enable_compilation_cache", False)
        _state.devices = devs
        _state.mesh = Mesh(np.array(devs), axis_names=(cfg.dp_axis_name,))

        from .utils.timeline import Timeline, rank_suffixed
        # rank stamps the clock_sync merge anchor so `timeline merge`
        # can rebase per-rank files onto one axis without filename hints.
        # np>1 additionally suffixes the path per rank (`/path.r3.json`)
        # — co-hosted workers handed one HOROVOD_TIMELINE path must not
        # clobber each other's traces; np=1 keeps the bare path.
        tl_path = cfg.timeline
        if tl_path:
            tl_path = rank_suffixed(tl_path, jax.process_index(),
                                    jax.process_count())
        _state.timeline = Timeline(tl_path,
                                   mark_cycles=cfg.timeline_mark_cycles,
                                   rank=jax.process_index())

        from .ops.engine import CollectiveEngine
        negotiator = None
        if cfg.controller_addr and jax.process_count() > 1:
            # Multi-process mode: engine cycles are coordinator-barriered so
            # fused dispatch order is identical on every process
            # († MPIController gather/bcast round).
            from .ops.negotiator import DistributedNegotiator
            host, _, port = cfg.controller_addr.rpartition(":")
            negotiator = DistributedNegotiator(
                host or "127.0.0.1", int(port), jax.process_index())
        _state.engine = CollectiveEngine(_state, negotiator)
        _state.engine.start()

        from .ops.process_sets import ProcessSetTable
        _state.process_set_table = ProcessSetTable(_state)

        # Metrics pull endpoint.  start() is first-call-wins process-wide:
        # if the env autostart in horovod_tpu.obs already bound a port,
        # a conflicting programmatic knob cannot rebind — say so instead
        # of silently serving on the old port.
        if cfg.metrics_port is not None:
            from .obs import server as obs_server
            try:
                srv = obs_server.start(cfg.metrics_port)
                if srv.port != cfg.metrics_port:
                    log.warning(
                        "metrics endpoint already on port %d (env "
                        "autostart); config metrics_port=%d ignored",
                        srv.port, cfg.metrics_port)
            except OSError as e:
                # Every worker of a multi-process job sees the same knob;
                # only one per host can bind it.  Telemetry is optional —
                # init must not fail over it.
                log.warning("metrics endpoint not started on port %d: %s",
                            cfg.metrics_port, e)

        # Obs plane: self-identifying info gauge + cluster aggregation.
        # Every scrape (and every aggregated snapshot) then answers
        # who/where/what-version without joining against launch logs.
        try:
            _arm_obs_plane()
            # Publish the engine-default wire precision as a gauge so a
            # scrape answers "is this job quantizing its allreduces".
            from .ops import reduction as _R
            _R.publish_mode_gauge(cfg.wire_precision)
        except Exception as e:  # telemetry must never fail init
            log.warning("obs plane not armed: %s", e)

        _state.initialized = True
        log.info(
            "horovod_tpu initialized: size=%d local_size=%d rank=%d backend=%s",
            _state.size, _state.local_size, _state.rank, jax.default_backend())


def _arm_obs_plane() -> None:
    """Register ``horovod_tpu_build_info`` and start the observability
    tiers that need runtime identity: cross-rank snapshot publishing /
    aggregation (:mod:`horovod_tpu.obs.aggregate`), the ``/healthz``
    readiness provider, the flight recorder's identity + auto-dump
    arming, the request tracer's sampling knob, and (when configured)
    the SLO engine.  Called under the init lock; re-entrant across
    elastic re-inits (a changed world size re-labels the info gauge and
    restarts the publisher/SLO engine)."""
    from . import __version__ as version
    from .obs import REGISTRY as obs_registry
    from .obs import aggregate as obs_aggregate
    from .obs import flightrec as obs_flightrec
    from .obs import perfmodel as obs_perfmodel
    from .obs import prof as obs_prof
    from .obs import server as obs_server
    from .obs import slo as obs_slo
    from .obs import trace as obs_trace

    cfg = _state.config
    dev = _state.devices[0]
    g = obs_registry.gauge(
        "horovod_tpu_build_info",
        "always 1; labels self-identify the scraped process "
        "(version/rank/world size/device kind)",
        ("version", "rank", "size", "device_kind"))
    # Elastic re-init can change rank/size: zero children from the old
    # world so only the current identity reads 1.
    g.zero_all()
    g.labels(version=version, rank=str(jax.process_index()),
             size=str(jax.process_count()),
             device_kind=getattr(dev, "device_kind", dev.platform)).set(1)
    # Elastic world-size gauges, refreshed on every (re-)rendezvous:
    # current_np is this epoch's actual world; target_np is what the
    # autoscaler asked for (the driver passes it down per launch) — the
    # two diverging on a scrape means a resize is in flight.
    obs_registry.gauge(
        "hvd_elastic_current_np",
        "world size of the running assignment").set(jax.process_count())
    _target = os.environ.get("HVDTPU_AUTOSCALE_TARGET_NP")
    if _target:
        try:
            obs_registry.gauge(
                "hvd_autoscale_target_np",
                "world size the autoscale policy currently wants",
                ("pool",),
            ).labels(pool="all").set(int(_target))
        except ValueError:
            pass
    obs_aggregate.start_for_rank(jax.process_index(), jax.process_count())

    # Request tracing: the config knob is the authoritative sample rate
    # (it already folded the env surface in).
    obs_trace.TRACER.sample_rate = cfg.trace_sample

    # Fleet trace plane: every rank publishes its ended-span table (and
    # timeline tail, when one is armed) + answers clock pings; /tracez
    # serves the merged Perfetto view (rank 0 is the canonical target,
    # mirroring /cluster).
    from .obs import tracemerge as obs_tracemerge
    obs_tracemerge.start_for_rank(
        jax.process_index(), jax.process_count(),
        pool=os.environ.get("HVDTPU_SERVING_POOL"),
        timeline_path=getattr(_state.timeline, "_path", None))

    # Flight recorder: identity for bundle headers; arming enables the
    # engine/elastic auto-dumps and the crash excepthook.
    obs_flightrec.RECORDER.set_identity(jax.process_index(),
                                        jax.process_count())
    obs_flightrec.RECORDER.set_capacity(cfg.flight_recorder_size)
    if cfg.flight_recorder_dir:
        obs_flightrec.RECORDER.arm(cfg.flight_recorder_dir)

    # Sampling profiler: always-on at the configured hz (0 disables);
    # re-entrant — elastic re-init retunes a live sampler in place.
    obs_prof.arm_from_config(cfg)

    # Performance model: the expected-cost denominator.  Configured link
    # model when the operator declared one; rolling-peak calibration
    # otherwise (the CPU rig default).
    obs_perfmodel.MODEL.configure(link_gbs=cfg.perf_link_gbs,
                                  link_latency_us=cfg.perf_link_latency_us)

    # SLO engine: declarative objectives evaluated against the registry;
    # gauges ride the snapshot path to /cluster with no extra wiring.
    if cfg.slo:
        obs_slo.arm(cfg.slo, tick_s=cfg.slo_tick_s)

    # Time-series tier: bounded in-memory history over the registry
    # (raw + 60s-downsampled rings) behind /query, flight-recorder
    # tails, and the autoscaler's forecasts; <= 0 disables.
    from .obs import tsdb as obs_tsdb
    if cfg.tsdb_interval_s > 0:
        obs_tsdb.arm(interval_s=cfg.tsdb_interval_s,
                     retention_s=cfg.tsdb_retention_s)
    else:
        obs_tsdb.disarm()

    # Declarative alerting over that history: pending->firing->resolved
    # per rule, firing gauges ride the snapshot path to /cluster,
    # transitions land in the flight recorder, state at /alertz.
    from .obs import alerts as obs_alerts
    if cfg.alerts:
        obs_alerts.arm(cfg.alerts)
    else:
        obs_alerts.disarm()

    # /healthz readiness: armed only while the runtime is up, so the
    # shutdown->init window of an elastic re-rendezvous answers 503 and
    # a router probe drops this replica from rotation.
    obs_server.set_health_provider(_health_snapshot)


_component_lock = threading.Lock()
_components: dict = {}


def set_component_health(name: str, ready, **info) -> None:
    """Subsystem readiness feeding ``/healthz``: any registered
    component reporting unready holds the whole probe at 503 (a serving
    session drains this way while it aborts and rejoins after an engine
    failure).  ``ready=None`` deregisters the component.  Components
    survive ``shutdown()`` — an elastic re-init must not forget that a
    serving session is still mid-drain."""
    with _component_lock:
        if ready is None:
            _components.pop(name, None)
        else:
            _components[name] = {"ready": bool(ready), **info}


def component_health(name: str):
    """One component's readiness: True/False as last reported, None when
    the component never registered (or deregistered).  The serving
    replica transport mirrors ``component_health("serving")`` into its
    published readiness gauge so the router sees drain windows."""
    with _component_lock:
        c = _components.get(name)
    return None if c is None else bool(c.get("ready"))


def _health_snapshot() -> dict:
    """The ``/healthz`` payload: is this rank able to serve/train right
    now, and how fresh is its view of the job."""
    eng = _state.engine
    alive = bool(eng is not None and eng.alive)
    ready = bool(_state.initialized and alive)
    status = "ok" if ready else "unready"
    d = {
        "rank": jax.process_index(),
        "size": jax.process_count(),
        "engine_alive": alive,
        "uptime_s": round(time.monotonic() - _START_MONO, 3),
    }
    if eng is not None:
        age = eng.last_negotiation_age_s
        d["last_negotiation_age_s"] = round(age, 3)
        limit = _state.config.health_max_negotiation_age_s
        if ready and limit > 0 and age > limit:
            # A wedged/stalled negotiation (peer withholding its
            # check-in, controller gone) means this rank cannot make
            # progress — answer 503 so probes pull it from rotation
            # before callers time out against it.
            ready = False
            status = "stalled"
    with _component_lock:
        comps = {k: dict(v) for k, v in _components.items()}
    if comps:
        d["components"] = comps
        down = [k for k, v in comps.items() if not v.get("ready")]
        if ready and down:
            ready = False
            status = "degraded:" + ",".join(sorted(down))
    d["ready"] = ready
    d["status"] = status
    return d


_START_MONO = time.monotonic()


def shutdown() -> None:
    """Stop the background engine († ``horovod_shutdown``)."""
    with _state.lock:
        if not _state.initialized:
            return
        from .obs import aggregate as obs_aggregate
        from .obs import alerts as obs_alerts
        from .obs import prof as obs_prof
        from .obs import server as obs_server
        from .obs import slo as obs_slo
        from .obs import tracemerge as obs_tracemerge
        from .obs import tsdb as obs_tsdb
        obs_aggregate.stop()
        obs_tracemerge.stop()
        obs_slo.disarm()
        obs_alerts.disarm()
        obs_tsdb.disarm()
        # Symmetric with the arm in init(): the sampler belongs to the
        # library lifecycle, not the process.
        obs_prof.PROFILER.stop()
        # /healthz answers 503 from here until the next init() — the
        # elastic re-rendezvous window a router probe must see as down.
        obs_server.set_health_provider(None)
        if _state.engine is not None:
            _state.engine.stop()
            _state.engine = None
        if _state.timeline is not None:
            _state.timeline.close()
            _state.timeline = None
        _state.mesh = None
        _state.devices = ()
        _state.process_set_table = None
        _state.initialized = False


atexit.register(shutdown)


def _require_init() -> _GlobalState:
    if not _state.initialized:
        raise NotInitializedError()
    return _state


def is_initialized() -> bool:
    return _state.initialized


def rank() -> int:
    """Global rank of this process's first device (†``horovod_rank``)."""
    return _require_init().rank


def size() -> int:
    """Total number of collective participants = devices (†``horovod_size``)."""
    return _require_init().size


def local_rank() -> int:
    """Process index on this host (†``horovod_local_rank``); 0 single-host."""
    _require_init()
    return jax.process_index()  # one process per host in TPU deployments


def local_size() -> int:
    """Number of devices driven by this process (†``horovod_local_size``)."""
    return _require_init().local_size


def cross_rank() -> int:
    """Host/process index across the job (†``horovod_cross_rank``)."""
    _require_init()
    return jax.process_index()


def cross_size() -> int:
    """Number of processes/hosts (†``horovod_cross_size``)."""
    _require_init()
    return jax.process_count()


def mesh() -> Mesh:
    """The persistent flat data-parallel mesh collectives dispatch on."""
    m = _require_init().mesh
    assert m is not None
    return m
