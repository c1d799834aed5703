"""Query server — the deployment daemon.

Parity target: ``core/.../workflow/CreateServer.scala``:

- deploy loads an EngineInstance (given ID or latest COMPLETED), rebuilds
  EngineParams from its params snapshot (``Engine.scala:419-489``),
  deserializes the persisted models and runs ``prepare_deploy``
  (``CreateServer.scala:213-272``)
- ``POST /queries.json`` = supplement → predict-per-algorithm → serve with
  the ORIGINAL query (``:510-661``), with per-query latency bookkeeping
- feedback loop POSTs a ``predict`` event (entityType ``pio_pr``) to the
  event server with the query/prediction payload (``:554-616``)
- ``POST /reload`` hot-swaps to the latest completed instance without
  dropping the listener (``MasterActor``, ``:352-378``)
- ``POST /stop`` undeploys; ``start()`` first undeploys any stale server
  on the same address, and retries bind 3× (``:295-330, 383-393``)

TPU adaptations: models are AOT-warmed at deploy so the first query never
pays an XLA compile (SURVEY hard part #4 — ``warmup_query`` in the server
config or a ``warmup_base`` hook on the algorithm); the akka actor tree is
replaced by a threaded HTTP server plus a lock-guarded engine swap.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import json
import logging
import secrets
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

from predictionio_tpu.controller.engine import (
    Engine,
    EngineParams,
    params_from_dict,
)
from predictionio_tpu.core.base import WorkflowParams
from predictionio_tpu.core.context import ComputeContext, workflow_context
from predictionio_tpu.data import storage
from predictionio_tpu.data.event import new_event_id
from predictionio_tpu.data.storage.base import EngineInstance, StorageError
from predictionio_tpu.ops.serving import QueryRejectedError
from predictionio_tpu.utils import metrics, resilience
from predictionio_tpu.utils.http_instrumentation import (
    InstrumentedHandlerMixin,
    SeveringThreadingHTTPServer,
)
from predictionio_tpu.utils.tracing import (
    LatencyHistogram,
    outbound_context_headers,
    span,
    trace_buffer,
    trace_scope,
)
from predictionio_tpu.workflow import core_workflow
from predictionio_tpu.workflow.server_plugins import EngineServerPluginContext

logger = logging.getLogger("pio.queryserver")

UTC = _dt.timezone.utc


@dataclasses.dataclass
class ServerConfig:
    """ServerConfig (CreateServer.scala:86-104)."""

    engine_instance_id: Optional[str] = None
    engine_id: str = "default"
    engine_version: str = "default"
    engine_variant: str = "engine.json"
    ip: str = "0.0.0.0"
    port: int = 8000
    feedback: bool = False
    event_server_ip: str = "0.0.0.0"
    event_server_port: int = 7070
    access_key: Optional[str] = None
    batch: str = ""
    warmup_query: Optional[Mapping[str, Any]] = None
    # server.json path with the TLS cert/key (the reference deploys
    # HTTPS-only via server.conf + SSLConfiguration,
    # CreateServer.scala:332-339 / SSLConfiguration.scala:50-72); None
    # checks $PIO_SERVER_CONFIG / ./server.json, and a file without an
    # "ssl" section serves plain HTTP
    server_config_path: Optional[str] = None
    # online fold-in (`pio deploy --foldin on`): a background consumer
    # tails the event stream and patches fresh user factors into the
    # live device store — see predictionio_tpu/online/foldin.py.
    # Cadence knobs: PIO_FOLDIN_INTERVAL / PIO_FOLDIN_COUNT.
    foldin: bool = False
    # SLO overrides for fleet mode (`pio deploy --fleet N
    # --slo-config ...`): inline JSON or a file path, layered over
    # defaults + $PIO_SLO_* — see predictionio_tpu/obs/slo.py
    slo_config: Optional[str] = None


class ReloadDowngradeError(RuntimeError):
    """``POST /reload`` refused: the latest completed instance is OLDER
    than the one deployed. With online fold-in live, an accidental
    downgrade throws away every folded user — the operator must
    undeploy/redeploy explicitly to roll back (rendered as HTTP 409).

    ``swapped`` — replicas a fleet roll had already swapped before the
    refusal aborted it (empty for a single server): the 409 body lists
    them so the operator sees exactly how far the roll got."""

    def __init__(self, *args: Any, swapped: Optional[List[Dict[str, Any]]] = None):
        super().__init__(*args)
        self.swapped: List[Dict[str, Any]] = list(swapped or [])


def engine_instance_to_engine_params(
        engine: Engine, instance: EngineInstance) -> EngineParams:
    """Rebuild EngineParams from the instance's JSON params snapshot
    (Engine.scala:419-489: engineInstanceToEngineParams)."""
    def one(snapshot: str, class_map, stage: str):
        block = json.loads(snapshot)
        name = block.get("name", "")
        if name not in class_map:
            raise ValueError(
                f"{stage}: controller named {name!r} from the engine "
                f"instance is not registered; known: {sorted(class_map)}")
        cls = class_map[name]
        return name, params_from_dict(
            getattr(cls, "params_class", None), block.get("params", {}),
            where=f"{stage}[{name!r}]")

    algo_blocks = json.loads(instance.algorithms_params)
    algos = []
    for i, block in enumerate(algo_blocks):
        algos.append(one(json.dumps(block), engine.algorithm_class_map,
                         f"algorithms[{i}]"))
    return EngineParams(
        data_source_params=one(instance.data_source_params,
                               engine.data_source_class_map, "datasource"),
        preparator_params=one(instance.preparator_params,
                              engine.preparator_class_map, "preparator"),
        algorithm_params_list=algos,
        serving_params=one(instance.serving_params,
                           engine.serving_class_map, "serving"),
    )


import functools


@functools.lru_cache(maxsize=4096)
def _camel(name: str) -> str:
    head, *rest = name.split("_")
    return head + "".join(w.capitalize() for w in rest)


@functools.lru_cache(maxsize=4096)
def _snake(name: str) -> str:
    """camelCase -> snake_case, cached: the same handful of field names
    recurs for every query of a bulk job."""
    out = []
    for ch in name:
        if ch.isupper():
            out.append("_")
            out.append(ch.lower())
        else:
            out.append(ch)
    return "".join(out)


_FIELD_CACHE: Dict[type, List[Tuple[str, str]]] = {}


def _fields_camel(cls: type) -> List[Tuple[str, str]]:
    """(snake field name, camel wire name) pairs per dataclass, cached —
    ``dataclasses.fields`` introspection per OBJECT made serialization
    the hottest line of bulk prediction (one call per nested score)."""
    cached = _FIELD_CACHE.get(cls)
    if cached is None:
        cached = [(f.name, _camel(f.name))
                  for f in dataclasses.fields(cls)]
        _FIELD_CACHE[cls] = cached
    return cached


def to_jsonable(obj: Any) -> Any:
    """Prediction/query → wire JSON. Dataclass fields go out camelCased
    (itemScores), matching the reference's case-class serialization style.

    Leaf scalars (every score/item string of a bulk top-K job) exit on
    the first check — the ABC ``Mapping`` isinstance they used to fall
    through was a measurable slice of batch-prediction wall time."""
    t = type(obj)
    if t is str or t is float or t is int or t is bool or obj is None:
        return obj
    if t is list or t is tuple:
        return [to_jsonable(v) for v in obj]
    cached = _FIELD_CACHE.get(t)
    if cached is not None:  # a dataclass seen before: skip introspection
        return {camel: to_jsonable(getattr(obj, name))
                for name, camel in cached}
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {
            camel: to_jsonable(getattr(obj, name))
            for name, camel in _fields_camel(t)
        }
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (np.floating, np.integer)):
        return obj.item()
    if isinstance(obj, Mapping):
        return {str(k): to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [to_jsonable(v) for v in obj]
    if isinstance(obj, _dt.datetime):
        return obj.isoformat()
    return obj


_QUERY_FIELDS: Dict[type, Tuple[str, ...]] = {}


def query_from_json(query_dict: Mapping[str, Any],
                    query_cls: Optional[type]) -> Any:
    """Typed-query extraction (JsonExtractor.extract analog): camelCase
    keys map onto the dataclass's snake_case fields; unknown/missing keys
    are explicit errors → 400. Field tables are cached per query class —
    this runs once per query of a bulk batch-predict job."""
    if query_cls is None or not dataclasses.is_dataclass(query_cls):
        return dict(query_dict)
    names = _QUERY_FIELDS.get(query_cls)
    if names is None:
        names = tuple(f.name for f in dataclasses.fields(query_cls))
        _QUERY_FIELDS[query_cls] = names
    data = {_snake(k): v for k, v in query_dict.items()}
    for name in names:
        # JSON arrays -> tuple fields
        if name in data and type(data[name]) is list:
            data[name] = tuple(data[name])
    return params_from_dict(query_cls, data, where=query_cls.__name__)


class Deployment:
    """One immutable deployed engine state; swapped atomically on reload.
    Shared by the query server and the batch-prediction engine
    (``predictionio_tpu/batch``) — both serve through the same loaded
    DASE state."""

    def __init__(self, instance: EngineInstance, engine: Engine,
                 engine_params: EngineParams, algorithms: List[Any],
                 models: List[Any], serving: Any):
        self.instance = instance
        self.engine = engine
        self.engine_params = engine_params
        self.algorithms = algorithms
        self.models = models
        self.serving = serving
        self.start_time = _dt.datetime.now(tz=UTC)


_Deployment = Deployment  # backwards-compatible private alias


def resolve_engine_instance(engine_instance_id: Optional[str],
                            engine_id: str = "default",
                            engine_version: str = "default",
                            engine_variant: str = "engine.json"
                            ) -> EngineInstance:
    """The given instance, or the latest COMPLETED one for the engine
    coordinates (CreateServer.scala:148-211 resolution order)."""
    instances = storage.get_metadata_engine_instances()
    if engine_instance_id:
        instance = instances.get(engine_instance_id)
        if instance is None:
            raise StorageError(
                f"engine instance {engine_instance_id!r} not found")
        return instance
    instance = instances.get_latest_completed(
        engine_id, engine_version, engine_variant)
    if instance is None:
        raise StorageError(
            "No valid engine instance found for engine "
            f"{engine_id} {engine_version} {engine_variant}. "
            "Try running train first.")
    return instance


def build_deployment(instance: EngineInstance, ctx: ComputeContext,
                     engine: Optional[Engine] = None,
                     batch: str = "") -> Deployment:
    """Load one engine instance into servable state
    (createServerActorWithEngine, CreateServer.scala:213-272): rebuild
    EngineParams from the params snapshot, deserialize + prepare_deploy
    the persisted models, validate the ensemble's query typing, and
    instantiate serving. Warm-up is the caller's choice (``warm_up``)."""
    if engine is None:
        factory = core_workflow.load_engine_factory(instance.engine_factory)
        engine = factory()
        from predictionio_tpu.controller.evaluation import Evaluation
        if isinstance(engine, Evaluation):
            engine = engine.engine
    engine_params = engine_instance_to_engine_params(engine, instance)

    with span("deploy.load_models"):
        blob = storage.get_model_data_models().get(instance.id)
        if blob is None:
            raise StorageError(
                f"no persisted models for engine instance {instance.id}")
        persisted = core_workflow.deserialize_models(blob.models)
        models = engine.prepare_deploy(
            ctx, engine_params, instance.id, persisted,
            params=WorkflowParams(batch=batch))

    algorithms = engine._algorithms(engine_params)
    # every ensemble member must agree on the query type: queries are
    # extracted with algorithms[0].query_class and fed to ALL of them
    # (CreateServer.scala:519-525 likewise types the whole server by
    # the first algorithm) — a silent mismatch would crash or
    # mis-parse at query time, so refuse at load
    declared = {a.query_class for a in algorithms
                if a.query_class is not None}
    if len(declared) > 1:
        names = sorted(c.__name__ for c in declared)
        raise ValueError(
            f"algorithms declare different query classes {names}; an "
            "ensemble must share one query type (the server extracts "
            "queries with the first algorithm's class)")
    if declared and algorithms[0].query_class is None:
        # a typed member behind an untyped first algorithm would
        # receive raw dicts — the same silent mismatch
        raise ValueError(
            f"algorithm {type(algorithms[0]).__name__} declares no "
            f"query class but a later ensemble member expects "
            f"{next(iter(declared)).__name__}; the first algorithm "
            "types query extraction for the whole server")
    sv_name, sv_params = engine_params.serving_params
    serving = engine._make(engine.serving_class_map, sv_name, sv_params,
                           "serving")
    from predictionio_tpu.controller.controllers import TwoStageServing
    if isinstance(serving, TwoStageServing):
        _bind_two_stage(serving, algorithms, models)
    return Deployment(instance, engine, engine_params, algorithms,
                      models, serving)


def _bind_two_stage(serving: Any, algorithms: List[Any],
                    models: List[Any]) -> None:
    """Fuse a ``TwoStageServing`` deployment onto ONE device store:
    build a :class:`~predictionio_tpu.ops.twostage.TwoStageTopK` over
    the retrieval model's factors AND the re-ranker's tables (loud
    policy validation inside — host backend, mismatched maps, and
    non-growable fold-in combos all refuse at load, never at query
    time), point each model's device-server handle at its facet of the
    store, and bind the serving's fused route so ``serve_query``
    dispatches retrieval + re-rank as one device program per query
    batch."""
    from predictionio_tpu.ops.twostage import build_two_stage_store

    if len(models) < 2:
        raise ValueError(
            "TwoStageServing needs EngineParams.algorithms = "
            "[retrieval, reranker] (at least two algorithms); got "
            f"{len(models)} — use LFirstServing for a single-algorithm "
            "deployment")
    retrieval, rerank = models[0], models[-1]
    store = build_two_stage_store(retrieval, rerank)
    retrieval._server = store.two_facet()
    # re-rank scores are transformer logits — a user whose candidates
    # all score negative still has a valid ranking, so the retrieval
    # model's implicit-ALS positivity filter must not drop them
    retrieval.serve_positive_scores_only = False
    rerank._server = store.seq_facet()
    algo0 = algorithms[0]
    serving.bind_fused(lambda q: algo0.predict_base(retrieval, q))


def warm_up(dep: Deployment,
            warmup_query: Optional[Mapping[str, Any]] = None) -> None:
    """AOT-compile the predict path before the first real query (SURVEY
    hard part #4): per-algorithm ``warmup_base`` hooks, then an optional
    sacrificial query through the full serve path.

    Bucket coverage is NOT enumerated here: every device-served model
    warms through ``DeviceTopK.warmup()``, which precompiles the full
    ``DeviceTopK.aot_plan()`` power-of-two ladder (every (k, batch)
    program live traffic can dispatch at). One enumeration, consulted
    by both deploy warm-up and the AOT precompiler, so they can never
    diverge — the old per-bucket warm loop here could (and did) warm
    only the default bucket. Models without a ``warmup_base`` hook but
    with a ``device_server()`` still get the ladder.

    The ladder is precision- and kernel-agnostic by construction: an
    int8 store (``pio deploy --serve-precision int8``) and the fused
    Pallas top-k programs (``--serve-kernel fused``, the TPU default)
    ride the same ``aot_plan()`` entries — the store signature and the
    program builders change underneath, the zero-serve-time-compile
    contract does not (every cell of ``benchmark/`` counts it as
    ``compiles_in_window``, tier-1 in ``tests/test_serving_load.py``)."""
    for algo, model in zip(dep.algorithms, dep.models):
        # no catch here: a device-served model whose ladder does not
        # compile must fail the deploy with the compiler's message, not
        # report ready and compile (or fail) on a live query
        warmup = getattr(algo, "warmup_base", None)
        if callable(warmup):
            warmup(model)
        else:
            # hook-less device-served models must not skip the
            # ladder: first queries would pay serve-time compiles
            device_server = getattr(model, "device_server", None)
            if callable(device_server):
                device_server().warmup()
    if warmup_query is not None:
        with span("deploy.warmup_query"):
            try:
                query = query_from_json(dict(warmup_query),
                                        dep.algorithms[0].query_class)
                serve_query(dep, query)
            except Exception:
                logger.exception("warmup query failed (non-fatal)")


def serve_query(dep: Deployment, query: Any) -> Any:
    """The single-query DASE serve path: supplement → predict per
    algorithm → serve with the ORIGINAL query (scala :538-540). Each
    stage is a trace span, so a slow query decomposes into the stage
    that cost it (the reference could only say "the query was slow")."""
    with span("serve.supplement"):
        supplemented = dep.serving.supplement_base(query)
    if getattr(dep.serving, "fused_bound", False):
        # two-stage fused deployments serve the whole query through
        # ONE device program (retrieval + re-rank never split): the
        # per-algorithm predict loop would dispatch the stages
        # separately and round-trip candidates through host
        with span("serve.fused",
                  attributes={"serving": type(dep.serving).__name__}):
            return dep.serving.serve_fused(supplemented)
    predictions = []
    for algo, model in zip(dep.algorithms, dep.models):
        with span("serve.predict",
                  attributes={"algorithm": type(algo).__name__}):
            predictions.append(algo.predict_base(model, supplemented))
    with span("serve.serve"):
        return dep.serving.serve_base(query, predictions)


_device_ok: Optional[bool] = None
_device_probe_at = 0.0
_device_probe_thread: Optional[threading.Thread] = None
_device_probe_lock = threading.Lock()
_DEVICE_PROBE_TIMEOUT = 10.0


def _device_reachable() -> bool:
    """Accelerator probe for readiness. SUCCESS is cached forever
    (device topology does not change under a live server, and a
    healthz poll must never pay a jax backend init); FAILURE is cached
    for 60s only — a backend that recovers must flip readiness back
    without a restart, but a dead one must not hang every poll.
    The probe itself runs on a daemon thread with a bounded join: a
    hung backend init BLOCKS inside jax.local_devices() forever (seen
    when the accelerator's transport is down), and
    healthz liveness is the response itself — it must always return.
    While a probe is still in flight, polls report not-ready without
    stacking further probe threads."""
    global _device_ok, _device_probe_at, _device_probe_thread
    if _device_ok:
        return True
    # the check-then-act is locked so concurrent polls spawn exactly
    # ONE probe thread; the probe is REGISTERED before the bounded join
    # so every other concurrent poll fails fast instead of stalling
    with _device_probe_lock:
        if _device_ok:
            return True
        now = time.monotonic()
        if _device_probe_thread is not None:
            if _device_probe_thread.is_alive():
                return False  # a probe is already wedged in backend init
            _device_probe_thread = None
        if _device_ok is False and now - _device_probe_at < 60.0:
            return False
        _device_probe_at = now

        def probe() -> None:
            global _device_ok
            try:
                import jax

                _device_ok = len(jax.local_devices()) > 0
            except Exception:
                _device_ok = False

        t = threading.Thread(target=probe, name="pio-device-probe",
                             daemon=True)
        t.start()
        _device_probe_thread = t
    t.join(_DEVICE_PROBE_TIMEOUT)
    with _device_probe_lock:
        if t.is_alive():  # hung: not ready; later polls see the thread
            return False
        if _device_probe_thread is t:
            _device_probe_thread = None
        return bool(_device_ok)


class QueryServer:
    """The deployment daemon (MasterActor + ServerActor combined)."""

    def __init__(self, config: ServerConfig,
                 engine: Optional[Engine] = None,
                 plugin_context: Optional[EngineServerPluginContext] = None,
                 ctx: Optional[ComputeContext] = None):
        self.config = config
        self._engine_override = engine
        self.plugin_context = plugin_context or EngineServerPluginContext()
        self.ctx = ctx or workflow_context(mode="serving", batch=config.batch)
        self._deployment: Optional[_Deployment] = None
        self._foldin = None  # online.foldin.FoldInConsumer when enabled
        self._foldin_env_prior: Optional[str] = None
        self._foldin_env_set = False
        self._swap_lock = threading.Lock()
        # per-SERVER latency (status page bookkeeping); every record also
        # feeds the process-wide per-variant registry histogram
        # (pio_query_seconds{variant=...}) — the reference's running
        # average (CreateServer.scala:438-440) generalized twice over
        self.latency = LatencyHistogram()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self.scheme = "http"  # resolved from server.json at start()
        self._profile_auth = None  # KeyAuthentication, set at start()

    # -- deploy ------------------------------------------------------------
    def _resolve_instance(self) -> EngineInstance:
        return resolve_engine_instance(
            self.config.engine_instance_id, self.config.engine_id,
            self.config.engine_version, self.config.engine_variant)

    def deploy(self) -> "QueryServer":
        """Load + warm the engine (createServerActorWithEngine,
        CreateServer.scala:213-272)."""
        # the serve-time compile monitor must be LIVE in a deployed
        # process (idempotent, no-op when metrics are off): the AOT
        # ladder's zero-compile contract is only checkable if
        # pio_jit_compiles_total actually counts — warm-up compiles
        # land in the counter, a flat counter under traffic proves no
        # query ever paid one
        metrics.install_jit_compile_listener()
        if self.config.foldin:
            # before the model loads: choose_server must see the policy
            # (fold-in needs the updatable DeviceTopK store) whether the
            # caller came through `pio deploy --foldin on` or built
            # ServerConfig(foldin=True) directly. The prior value is
            # restored by stop() — an embedder's NEXT deployment in the
            # same process must not inherit this one's policy
            import os

            if not self._foldin_env_set:
                self._foldin_env_prior = os.environ.get("PIO_FOLDIN")
                self._foldin_env_set = True
            os.environ["PIO_FOLDIN"] = "1"
        try:
            # one local root per deploy: load_models, store.*, ladder.*
            # and the warm-up query are its spans, so set-up decomposes
            # without a stopwatch round this call
            with trace_scope("pio.deploy", slow_exempt=True):
                instance = self._resolve_instance()
                self._deployment = self._build_deployment(instance)
            if self.config.foldin:
                self._start_foldin()
        except BaseException:
            # a FAILED deploy must not leak the policy into the
            # process (stop() only covers the success path)
            self._restore_foldin_env()
            raise
        logger.info("Engine instance %s deployed", instance.id)
        return self

    def _restore_foldin_env(self) -> None:
        if not self._foldin_env_set:
            return
        import os

        if self._foldin_env_prior is None:
            os.environ.pop("PIO_FOLDIN", None)
        else:
            os.environ["PIO_FOLDIN"] = self._foldin_env_prior
        self._foldin_env_set = False

    def _start_foldin(self, deployment=None) -> None:
        """(Re)start the online fold-in consumer against ``deployment``
        (default: the current one). The NEW consumer starts before the
        old one stops — attach/start raising therefore leaves the old
        consumer running untouched, which lets reload() validate the
        candidate deployment's fold-in BEFORE committing the swap. The
        brief overlap is harmless: the old consumer patches the old
        model's store, which is about to be dropped."""
        from predictionio_tpu.online.foldin import attach_foldin

        dep = deployment if deployment is not None else self._deployment
        assert dep is not None
        new = attach_foldin(dep).start()
        if self._foldin is not None:
            self._foldin.stop()
        self._foldin = new

    def _build_deployment(self, instance: EngineInstance) -> Deployment:
        dep = build_deployment(instance, self.ctx,
                               engine=self._engine_override,
                               batch=self.config.batch)
        self._warm_up(dep)
        return dep

    def _warm_up(self, dep: Deployment) -> None:
        """AOT-compile the predict path before the first real query."""
        warm_up(dep, self.config.warmup_query)

    # -- the query path (CreateServer.scala:510-661) -----------------------
    def _serve_one(self, dep: _Deployment,
                   query_dict: Mapping[str, Any]) -> Tuple[Any, Any]:
        query = self._extract_query(dep, query_dict)
        return query, self._predict(dep, query)

    @staticmethod
    def _predict(dep: Deployment, query: Any) -> Any:
        # by design: serve with the *original* query (scala :538-540)
        return serve_query(dep, query)

    @staticmethod
    def _extract_query(dep: _Deployment,
                       query_dict: Mapping[str, Any]) -> Any:
        return query_from_json(query_dict, dep.algorithms[0].query_class)

    def handle_query(self, body: bytes) -> Tuple[int, Any]:
        dep = self._deployment
        assert dep is not None, "not deployed"
        t0 = time.perf_counter()
        query_time = _dt.datetime.now(tz=UTC)
        try:
            with span("query.parse"):
                query_dict = json.loads(body.decode("utf-8"))
            if not isinstance(query_dict, dict):
                raise ValueError("query must be a JSON object")
        except (json.JSONDecodeError, UnicodeDecodeError, ValueError) as e:
            return 400, {"message": f"{e}"}
        # extraction errors are the client's fault (400, scala :644-651);
        # anything thrown past extraction is an engine failure (500)
        try:
            with span("query.extract"):
                query = self._extract_query(dep, query_dict)
        except (ValueError, TypeError) as e:
            logger.error("Query %r is invalid. Reason: %s", query_dict, e)
            return 400, {"message": str(e)}
        try:
            # graceful degradation: predict-time storage reads that
            # fail (event store down, breaker open, deadline hit) mark
            # the scope instead of failing the query — the device
            # factor store still answers, and the response says so
            with resilience.degraded_scope() as degraded:
                foldin = self._foldin
                if foldin is not None and foldin.stale:
                    # the fold-in tail is failing: answers come from
                    # the last-good factors (PR-7 semantics — serve,
                    # but say so)
                    resilience.mark_degraded("foldin_stale")
                prediction = self._predict(dep, query)
        except QueryRejectedError as e:
            # queue overload: fail FAST with the server's own pacing
            # hint, never an opaque 500 (micro-batcher deadline)
            return 503, {"message": str(e),
                         "retryAfterSec": e.retry_after}
        except Exception as e:
            logger.exception("query failed")
            return 500, {"message": str(e)}

        with span("query.render"):
            result = self._render(dep, query_dict, query, prediction,
                                  degraded, query_time)

        took = time.perf_counter() - t0
        self.latency.record(took)
        metrics.QUERY_LATENCY.observe(took,
                                      variant=self.config.engine_variant)
        return 200, result

    def _render(self, dep: _Deployment, query_dict: Mapping[str, Any],
                query: Any, prediction: Any, degraded: List[str],
                query_time: _dt.datetime) -> Any:
        """Prediction -> wire JSON, then what may rewrite it: the
        degraded marks, the feedback loop's ``prId``, output plugins."""
        result = to_jsonable(prediction)
        if degraded:
            # the query WAS served degraded whatever its result shape —
            # count always; the response field needs a JSON object
            for reason in degraded:
                metrics.DEGRADED_QUERIES.inc(reason=reason)
            if isinstance(result, dict):
                result["degraded"] = True
                result["degradedReasons"] = list(degraded)
        if self.config.feedback:
            result = self._feedback(dep, query_dict, query, prediction,
                                    result, query_time)
        for blocker in self.plugin_context.output_blockers.values():
            result = blocker.process(dep.instance, query_dict, result,
                                     self.plugin_context)
        for sniffer in self.plugin_context.output_sniffers.values():
            try:
                sniffer.process(dep.instance, query_dict, result,
                                self.plugin_context)
            except Exception:
                logger.exception("output sniffer failed")
        return result

    def _feedback(self, dep: _Deployment, query_dict: Mapping[str, Any],
                  query: Any, prediction: Any, result: Any,
                  query_time: _dt.datetime) -> Any:
        """Async predict-event POST to the event server
        (CreateServer.scala:554-616)."""
        org = getattr(prediction, "pr_id", None) or query_dict.get("prId")
        pr_id = org or secrets.token_hex(32)
        data = {
            "event": "predict",
            # client-generated id = idempotency key: if the retried
            # POST's first attempt committed before its response was
            # lost, id-keyed backends dedup instead of double-counting
            "eventId": new_event_id(),
            "eventTime": query_time.isoformat(),
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": {
                "engineInstanceId": dep.instance.id,
                "query": to_jsonable(query),
                "prediction": result,
            },
        }
        if "prId" in query_dict:
            data["prId"] = query_dict["prId"]
        url = (f"http://{self.config.event_server_ip}:"
               f"{self.config.event_server_port}/events.json"
               f"?accessKey={self.config.access_key or ''}")
        # capture the request's observability context NOW (the POST runs
        # on a detached thread after the response is gone): the event
        # server's spans for the feedback insert join the query's trace
        headers = {"Content-Type": "application/json",
                   **outbound_context_headers()}
        body = json.dumps(data).encode("utf-8")

        def post():
            # bounded: ONE retry, then drop with a counter. Feedback is
            # telemetry — it runs on a detached daemon thread and must
            # never delay or fail the query response, so an unreachable
            # event server costs at most two short attempts here.
            last: Optional[Exception] = None
            for attempt in range(2):
                try:
                    req = urllib.request.Request(
                        url, data=body, headers=headers, method="POST")
                    with urllib.request.urlopen(req, timeout=5) as resp:
                        if resp.status == 201:
                            return
                        # 2xx/3xx that is not 201 — a retry with the
                        # same payload cannot change the server's mind
                        logger.error(
                            "Feedback event failed. Status code: %d. "
                            "Data: %s.", resp.status, data)
                        metrics.FEEDBACK_DROPPED.inc()
                        return
                except urllib.error.HTTPError as e:
                    if e.code < 500:
                        # the server REFUSED (4xx = our payload's
                        # fault): retrying the identical payload is
                        # pointless — drop now
                        logger.error(
                            "Feedback event refused (%d). Data: %s.",
                            e.code, data)
                        metrics.FEEDBACK_DROPPED.inc()
                        return
                    last = e
                    if attempt == 0:
                        time.sleep(0.2)
                except Exception as e:
                    last = e
                    if attempt == 0:
                        time.sleep(0.2)
            metrics.FEEDBACK_DROPPED.inc()
            logger.error("Feedback event dropped after retry: %s", last)

        threading.Thread(target=post, daemon=True,
                         name="pio-feedback").start()
        # inject prId into the response when the prediction carries one
        if hasattr(prediction, "pr_id") and isinstance(result, dict):
            result = dict(result, prId=pr_id)
        return result

    # -- reload / status ---------------------------------------------------
    def reload(self) -> Dict[str, Any]:
        """Hot-swap to the latest completed instance
        (MasterActor ReloadServer, CreateServer.scala:352-378).

        Hardened for the fold-in era: the response names BOTH instance
        ids (swapped-from/to — an operator must be able to tell a real
        swap from a same-instance re-deploy), and a swap to an instance
        OLDER than the one deployed is refused (409) — with online
        fold-in live, a silent downgrade discards every user folded
        since the newer train."""
        with self._swap_lock:
            current = self._deployment
            instances = storage.get_metadata_engine_instances()
            latest = instances.get_latest_completed(
                self.config.engine_id, self.config.engine_version,
                self.config.engine_variant)
            if latest is None:
                raise StorageError("No valid engine instance found for "
                                   "reload")
            if current is not None and latest.id != current.instance.id \
                    and latest.start_time < current.instance.start_time:
                raise ReloadDowngradeError(
                    f"refusing to reload: latest completed instance "
                    f"{latest.id} (started "
                    f"{latest.start_time.isoformat()}) is OLDER than the "
                    f"deployed {current.instance.id} (started "
                    f"{current.instance.start_time.isoformat()}); "
                    "undeploy and redeploy explicitly to downgrade")
            candidate = self._build_deployment(latest)
            if self.config.foldin:
                # validate the candidate's fold-in BEFORE the swap: if
                # the new deployment cannot be tailed (non-ALSParams
                # algorithm, missing app_name), the reload fails with
                # the OLD deployment and its consumer fully intact —
                # never a live swap with fold-in silently dead
                self._start_foldin(candidate)
            self._deployment = candidate
            return {
                "engineInstanceId": latest.id,
                "swappedFrom": None if current is None
                else current.instance.id,
                "swappedTo": latest.id,
            }

    def status(self) -> Dict[str, Any]:
        dep = self._deployment
        summary = self.latency.summary()
        # snapshot: a concurrent stop() nulls self._foldin between a
        # check and a call (same pattern as the predict path)
        consumer = self._foldin
        foldin = consumer.stats() if consumer is not None else None
        return {
            "foldin": foldin,
            "status": "alive",
            "engineInstanceId": dep.instance.id if dep else None,
            "engineFactory": dep.instance.engine_factory if dep else None,
            "startTime": dep.start_time.isoformat() if dep else None,
            "algorithms": [type(a).__name__ for a in dep.algorithms]
            if dep else [],
            "feedback": self.config.feedback,
            # reference status fields (CreateServer.scala:438-440) derived
            # from the histogram, which owns all latency bookkeeping
            "requestCount": summary.get("count", 0),
            "avgServingSec": summary.get("meanSec", 0.0),
            "lastServingSec": summary.get("lastSec", 0.0),
            "servingLatency": summary,
        }

    def stats_json(self) -> Dict[str, Any]:
        """GET /stats.json: the status page, the live micro-batch
        lanes' unified ``batcher_stats`` (dispatch triggers, batch-fill
        ratio, queue-depth percentiles — one shape for user and item
        lanes), the ``stages`` block (median self time per span name
        over the newest query roots, and under ``lock`` what threads
        waited for the span buffer's lock), the ``device`` block (store +
        AOT ladder HBM bytes,
        ladder coverage, flight-recorder dispatch summary), plus the
        process-wide registry snapshot (pio_query_seconds,
        pio_microbatch_*, pio_storage_op_* ... — the same state
        GET /metrics renders as Prometheus text)."""
        from predictionio_tpu.fleet.balancer import _storage_topology
        from predictionio_tpu.ops import serving as _serving

        out = {**self.status(),
               "batchers": _serving.batcher_stats(),
               "device": _serving.device_report(),
               # where a query's time goes inside this server: median
               # self time per span over the newest query roots
               "stages": trace_buffer().stage_p50(
                   "query POST /queries.json"),
               "metrics": metrics.registry().snapshot()}
        # when EVENTDATA is the sharded fleet source, surface the shard
        # topology (per-shard breaker states, partial-read count) here
        topo = _storage_topology()
        if topo is not None:
            out["storageFleet"] = topo
        return out

    def dispatches_json(self, limit: int = 100) -> Dict[str, Any]:
        """GET /dispatches.json: the device-plane flight recorder —
        the last N dispatches (lane, bucket shape, batch/fill,
        precision, kernel, AOT hit/miss, queue wait, host + device µs,
        the dispatcher thread's stage stamps, the delivered queries'
        ``lives``) plus per-lane summaries (percentiles, each stage's
        sum) and what threads waited for the ring's lock."""
        from predictionio_tpu.utils import device_telemetry

        return device_telemetry.recorder().report(limit=limit)

    def profile_start(self) -> Dict[str, Any]:
        """POST /profile/start: begin a single-flight jax.profiler
        capture on the LIVE server (written next to the --trace-dir
        exports). A second start while one runs raises (HTTP 409)."""
        from predictionio_tpu.utils.tracing import PROFILER

        return {"message": "profiler capture started",
                "profileDir": PROFILER.start()}

    def profile_stop(self) -> Dict[str, Any]:
        """POST /profile/stop: end the active capture; 409 when none
        is running."""
        from predictionio_tpu.utils.tracing import PROFILER

        return {"message": "profiler capture written",
                **PROFILER.stop()}

    def health_checks(self) -> Dict[str, bool]:
        """Readiness for ``GET /healthz``: a deployment is loaded, the
        accelerator answers, and the event-store breaker is not
        refusing calls. Liveness is the response itself; readiness
        going false tells the balancer to drain THIS replica while it
        keeps serving (degraded) what it can."""
        checks = {"deployment": self._deployment is not None,
                  "device": _device_reachable()}
        checks["storage"] = resilience.storage_ready(storage.get_levents)
        return checks

    # -- HTTP lifecycle ----------------------------------------------------
    def start(self, undeploy_stale: bool = True,
              bind_retries: int = 3) -> "QueryServer":
        # TLS config first: the stale-server probe and the bind wrap both
        # depend on the scheme (CreateServer.scala:332-339 — the
        # reference deploys HTTPS via server.conf + SSLConfiguration)
        from predictionio_tpu.common import SSLConfiguration
        from predictionio_tpu.common.auth import (
            KeyAuthentication,
            ServerConfig as AuthServerConfig,
        )

        auth_cfg = AuthServerConfig.load(self.config.server_config_path)
        # the profiler-capture endpoints are operator actions on a live
        # server: when server.json configures an accessKey they require
        # it (KeyAuthentication, the dashboard's rule); without one the
        # server is open, matching every other operator surface here
        self._profile_auth = KeyAuthentication(auth_cfg)
        sslc = SSLConfiguration(auth_cfg)
        self.scheme = "https" if sslc.enabled else "http"
        if self._deployment is None:
            self.deploy()
        if undeploy_stale:
            # a stale server may run the OTHER scheme (operator just
            # added/removed TLS); probe both so the port always frees
            if not undeploy(self.config.ip, self.config.port,
                            scheme=self.scheme):
                undeploy(self.config.ip, self.config.port,
                         scheme="http" if self.scheme == "https"
                         else "https")
        server = self

        class Handler(_QueryHandler):
            query_server = server

        last_err: Optional[Exception] = None
        for attempt in range(bind_retries):
            try:
                self._httpd = SeveringThreadingHTTPServer(
                    (self.config.ip, self.config.port), Handler)
                break
            except OSError as e:  # bind failure, retry (scala :383-393)
                last_err = e
                logger.warning("Bind failed (attempt %d): %s", attempt + 1, e)
                time.sleep(1.0)
        else:
            raise RuntimeError(
                f"Bind failed after {bind_retries} tries") from last_err
        if sslc.enabled:
            # wrap the listener exactly as the dashboard does
            sslc.wrap_server(self._httpd)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="pio-queryserver",
            daemon=True)
        self._thread.start()
        logger.info("Query server started on %s://%s:%d", self.scheme,
                    *self.address)
        return self

    @property
    def address(self) -> Tuple[str, int]:
        assert self._httpd is not None, "server not started"
        host, port = self._httpd.server_address[:2]
        return str(host), int(port)

    def stop(self) -> None:
        if self._foldin is not None:
            self._foldin.stop()
            self._foldin = None
        self._restore_foldin_env()
        if self._httpd is not None:
            httpd, self._httpd = self._httpd, None
            httpd.shutdown()  # stops serve_forever, THEN close the socket
            httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def serve_forever(self) -> None:
        if self._httpd is None:
            self.start()
        assert self._thread is not None
        self._thread.join()


def undeploy(ip: str, port: int, scheme: str = "http") -> bool:
    """POST /stop to a stale server before binding
    (CreateServer.scala:295-330). True if something answered. With
    ``scheme="https"`` certificate verification is skipped: the probe
    talks to our own (commonly self-signed) stale instance on a local
    port, and the only action is asking it to stop."""
    import ssl as _ssl

    host = "127.0.0.1" if ip == "0.0.0.0" else ip
    kwargs = {}
    if scheme == "https":
        ctx = _ssl.create_default_context()
        ctx.check_hostname = False
        ctx.verify_mode = _ssl.CERT_NONE
        kwargs["context"] = ctx
    try:
        req = urllib.request.Request(
            f"{scheme}://{host}:{port}/stop", data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=3, **kwargs) as resp:
            logger.info("Undeployed stale server at %s:%d (%d)",
                        host, port, resp.status)
            return True
    except (urllib.error.URLError, OSError):
        return False


class _QueryHandler(InstrumentedHandlerMixin, BaseHTTPRequestHandler):
    query_server: QueryServer
    protocol_version = "HTTP/1.1"
    metrics_server_label = "query"

    def log_message(self, fmt, *args):
        logger.debug("%s - %s", self.address_string(), fmt % args)

    def _drain(self) -> bytes:
        with span("http.read_body"):
            length = int(self.headers.get("Content-Length") or 0)
            return self.rfile.read(length) if length else b""

    _ROUTES = ("/", "/healthz", "/metrics", "/stats.json",
               "/dispatches.json", "/plugins.json", "/queries.json",
               "/profile/start", "/profile/stop", "/reload", "/stop",
               "/traces.json")

    def _route_label(self, path: str) -> str:
        if path.startswith("/traces/"):
            return "/traces/<id>"
        return path if path in self._ROUTES else "<other>"

    def _dispatch(self, method: str) -> None:
        parsed = urllib.parse.urlsplit(self.path)
        path = parsed.path.rstrip("/") or "/"
        query = urllib.parse.parse_qs(parsed.query)
        handle = (lambda: self._do_get(path, query)) if method == "GET" \
            else (lambda: self._do_post(path, query))
        self._dispatch_instrumented(method, path, handle)

    def _do_get(self, path: str, query) -> None:
        srv = self.query_server
        self._drain()
        if path == "/":
            self._respond(200, srv.status())
        elif path == "/healthz":
            self._respond_healthz(srv.health_checks())
        elif path == "/metrics":
            self._respond_prometheus()
        elif path == "/stats.json":
            self._respond(200, srv.stats_json())
        elif path == "/dispatches.json":
            try:
                # the recorder returns at most what its ring retains
                # ($PIO_DEVICE_TELEMETRY_RING)
                limit = int(self._q_first(query, "limit") or 100)
            except ValueError:
                limit = 100
            self._respond(200, srv.dispatches_json(limit=limit))
        elif path == "/traces.json":
            self._respond_traces_index(query)
        elif path.startswith("/traces/"):
            self._respond_trace(path[len("/traces/"):], query)
        elif path == "/plugins.json":
            self._respond(200, srv.plugin_context.describe())
        else:
            self._respond(404, {"message": "Not Found"})

    def _do_post(self, path: str, query=None) -> None:
        srv = self.query_server
        body = self._drain()
        try:
            if path in ("/profile/start", "/profile/stop"):
                self._handle_profile(path, query or {})
            elif path == "/queries.json":
                status, payload = srv.handle_query(body)
                if status == 503 and isinstance(payload, dict) \
                        and payload.get("retryAfterSec") is not None:
                    # overload rejections carry the standard header so
                    # plain HTTP clients back off without parsing JSON
                    retry_in = max(1, int(payload["retryAfterSec"]))
                    self._respond_bytes(
                        status, json.dumps(payload).encode("utf-8"),
                        "application/json; charset=UTF-8",
                        extra_headers={"Retry-After": str(retry_in)})
                else:
                    self._respond(status, payload)
            elif path == "/reload":
                try:
                    info = srv.reload()
                except ReloadDowngradeError as e:
                    self._respond(409, {"message": str(e)})
                    return
                self._respond(200, {"message": "Reloading...", **info})
            elif path == "/stop":
                # the server is about to die: tell keep-alive clients
                # (HTTP/1.1 connections persist by default) not to
                # reuse this connection, and close it after the
                # response instead of waiting out the read timeout
                self.close_connection = True
                self._respond_bytes(
                    200,
                    json.dumps({"message": "Shutting down."})
                    .encode("utf-8"),
                    "application/json; charset=UTF-8",
                    extra_headers={"Connection": "close"})
                threading.Thread(target=srv.stop, daemon=True).start()
            else:
                self._respond(404, {"message": "Not Found"})
        except Exception as e:
            logger.exception("unhandled error on POST %s", path)
            try:
                self._respond(500, {"message": str(e)})
            except Exception:
                pass

    def _handle_profile(self, path: str, query) -> None:
        """On-demand profiler capture: authed (server.json accessKey,
        when configured), single-flight — a second start, or a stop
        with nothing running, is 409."""
        from predictionio_tpu.utils.tracing import (
            ProfilerBusyError,
            ProfilerNotRunningError,
        )

        srv = self.query_server
        auth = srv._profile_auth
        if auth is not None and not auth.authenticate(query):
            self._respond(403, {"message": "invalid accessKey"})
            return
        try:
            if path == "/profile/start":
                self._respond(200, srv.profile_start())
            else:
                self._respond(200, srv.profile_stop())
        except (ProfilerBusyError, ProfilerNotRunningError) as e:
            self._respond(409, {"message": str(e)})

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")


def create_server(config: ServerConfig, **kwargs) -> QueryServer:
    """CreateServer.main analog (CreateServer.scala:119-211)."""
    return QueryServer(config, **kwargs)
