"""``pio`` lifecycle verbs: build, train, eval, deploy, undeploy,
eventserver.

Parity: ``tools/.../console/Console.scala`` dispatch (:698-769) with the
spark-submit/Runner layer removed — train/eval/deploy run in this host
process (SURVEY §7: "the runner IS the TPU host process").

Engine location: a directory with an ``engine.json`` variant whose
``engineFactory`` names a ``module:callable`` (the sbt-built jar +
manifest of the reference collapses to an importable Python package).
"""

from __future__ import annotations

import datetime as _dt
import json
import os
import sys
from typing import Any, Dict, Optional

from predictionio_tpu.workflow.create_workflow import WorkflowConfig


def _load_variant(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def _workflow_config(args, variant: Dict[str, Any]) -> WorkflowConfig:
    factory = getattr(args, "engine_factory", None) or variant.get(
        "engineFactory", "")
    if not factory:
        raise ValueError(
            "no engine factory: set \"engineFactory\": \"module:callable\" "
            "in engine.json or pass --engine-factory")
    return WorkflowConfig(
        engine_id=getattr(args, "engine_id", None) or variant.get(
            "id", "default"),
        engine_version=getattr(args, "engine_version", None) or variant.get(
            "version", "default"),
        engine_variant=args.engine_variant,
        engine_factory=factory,
        batch=getattr(args, "batch", "") or "",
        skip_sanity_check=getattr(args, "skip_sanity_check", False),
        stop_after_read=getattr(args, "stop_after_read", False),
        stop_after_prepare=getattr(args, "stop_after_prepare", False),
    )


def cmd_build(args) -> int:
    """Sanity-check the engine dir: variant parses, factory imports, params
    typecheck (the sbt build + RegisterEngine analog, Console.scala:812-828)."""
    from predictionio_tpu.controller.evaluation import Evaluation
    from predictionio_tpu.workflow import core_workflow

    try:
        variant = _load_variant(args.engine_variant)
        config = _workflow_config(args, variant)
        factory = core_workflow.load_engine_factory(config.engine_factory)
        engine = factory()
        if isinstance(engine, Evaluation):
            engine = engine.engine
        engine.engine_params_from_variant(variant)
    except Exception as e:
        print(f"[ERROR] {e}", file=sys.stderr)
        return 1
    print("[INFO] Engine is ready for training.")
    return 0


def _apply_metrics_flag(args) -> None:
    """--metrics on|off -> the process-wide registry switch (None leaves
    the PIO_METRICS env default in place)."""
    flag = getattr(args, "metrics", None)
    if flag is not None:
        from predictionio_tpu.utils import metrics
        metrics.set_enabled(flag == "on")


def _apply_tracing_flags(args) -> None:
    """--tracing on|off + --trace-dir/$PIO_TRACE_DIR -> the tracing
    switch and the JSONL trace export (None leaves PIO_TRACING alone)."""
    from predictionio_tpu.utils import tracing

    flag = getattr(args, "tracing", None)
    if flag is not None:
        tracing.set_tracing_enabled(flag == "on")
    trace_dir = getattr(args, "trace_dir", None) \
        or os.environ.get("PIO_TRACE_DIR") or None
    if trace_dir:
        tracing.set_trace_dir(trace_dir)


def _apply_precision_flags(args) -> None:
    """--precision -> $PIO_ALS_PRECISION, --serve-precision ->
    $PIO_SERVE_PRECISION. The env vars are the single source of truth
    the per-call resolvers (ops/als.py, ops/serving.py) read, so the
    flags override engine.json params the same way the operator-set env
    would; None leaves any ambient env value in place."""
    precision = getattr(args, "precision", None)
    if precision:
        os.environ["PIO_ALS_PRECISION"] = precision
    serve_precision = getattr(args, "serve_precision", None)
    if serve_precision:
        os.environ["PIO_SERVE_PRECISION"] = serve_precision
    serve_kernel = getattr(args, "serve_kernel", None)
    if serve_kernel:
        os.environ["PIO_SERVE_KERNEL"] = serve_kernel


def _apply_checkpoint_flags(args) -> None:
    """--checkpoint-every/-dir/-keep + --resume -> the PIO_CHECKPOINT_*
    env vars the per-call resolver (workflow/checkpoint.py) reads —
    the same env-as-truth discipline as the precision flags. When a
    checkpoint dir is active, SIGTERM/SIGINT become graceful
    preemption: finish the in-flight chunk, write a final checkpoint,
    exit 0."""
    every = getattr(args, "checkpoint_every", None)
    if every is not None and every < 1:
        raise SystemExit("--checkpoint-every must be >= 1")
    keep = getattr(args, "checkpoint_keep", None)
    if keep is not None and keep < 1:
        raise SystemExit("--checkpoint-keep must be >= 1")
    cdir = getattr(args, "checkpoint_dir", None)
    resume = bool(getattr(args, "resume", False))
    active_dir = (cdir or os.environ.get("PIO_CHECKPOINT_DIR", "")).strip()
    if (every is not None or resume) and not active_dir:
        raise SystemExit(
            "--checkpoint-every/--resume require --checkpoint-dir "
            "(or $PIO_CHECKPOINT_DIR)")
    # validation complete — only now touch the env: a refused
    # invocation must not leave half the knobs set behind it (in-
    # process callers would inherit a phantom $PIO_RESUME)
    if every is not None:
        os.environ["PIO_CHECKPOINT_EVERY"] = str(every)
    if cdir:
        os.environ["PIO_CHECKPOINT_DIR"] = cdir
    if keep is not None:
        os.environ["PIO_CHECKPOINT_KEEP"] = str(keep)
    if resume:
        os.environ["PIO_RESUME"] = "1"
    # graceful-drain handlers ONLY when a chunk cadence is actually
    # configured here (flag/env every, or --resume): a dir alone runs
    # the single-scan path with no boundary that would ever honor the
    # stop flag, and a swallowed SIGTERM that logs "will checkpoint"
    # while nothing will is worse than the default kill. (An engine
    # variant may still set ALSParams.checkpoint_every on its own —
    # checkpoints then land at every boundary and a hard kill stays
    # resumable; only the signal-drain nicety needs the CLI/env knob.)
    if active_dir and (
            every is not None or resume
            or os.environ.get("PIO_CHECKPOINT_EVERY", "").strip()):
        from predictionio_tpu.workflow import checkpoint

        checkpoint.clear_stop()
        checkpoint.install_signal_handlers()


def _train_progress_scope():
    """The `pio train` live meter: renders each chunk-boundary
    telemetry sample as a single ``\\r``-rewritten progress line on
    stderr. Active when stderr is a TTY, forced on/off with
    $PIO_TRAIN_PROGRESS; a plain nullcontext under
    PIO_TRAIN_TELEMETRY=0 (no samples would arrive anyway)."""
    import contextlib

    from predictionio_tpu.workflow import checkpoint, runlog

    forced = os.environ.get("PIO_TRAIN_PROGRESS", "").strip().lower()
    if forced in ("0", "false", "no", "off") \
            or not runlog.telemetry_enabled() \
            or not (forced in ("1", "true", "yes", "on")
                    or sys.stderr.isatty()):
        return contextlib.nullcontext()

    state = {"width": 0}

    def render(p):
        total = int(p.get("total") or 0)
        step = int(p.get("step") or 0)
        bar_w = 24
        fill = min(bar_w, int(bar_w * step / total)) if total else 0
        loss = p.get("loss")
        msg = (f"[{'#' * fill}{'-' * (bar_w - fill)}] "
               f"iter {step}/{total} "
               f"loss {'-' if loss is None else f'{loss:.6g}'} "
               f"({float(p.get('wallSeconds') or 0):.2f}s/chunk)")
        sys.stderr.write("\r" + msg.ljust(state["width"]))
        state["width"] = len(msg)
        if total and step >= total:
            sys.stderr.write("\n")
            state["width"] = 0
        sys.stderr.flush()

    return checkpoint.progress_scope(render)


def cmd_train(args) -> int:
    """Console train (Console.scala:834-842) -> create_workflow. A
    profile dir (--profile-dir / $PIO_PROFILE_DIR) captures a
    jax.profiler trace of the whole train pass, with JIT-compile
    count/time accounted in the metrics registry."""
    from predictionio_tpu.core.base import TrainingInterruption
    from predictionio_tpu.utils import metrics
    from predictionio_tpu.workflow.create_workflow import create_workflow

    from predictionio_tpu.utils.tracing import profile_trace, trace_scope

    _apply_tracing_flags(args)
    _apply_precision_flags(args)
    _apply_checkpoint_flags(args)
    try:
        # multi-host runtime (no-op on one host; parallel/distributed.py)
        from predictionio_tpu.parallel import distributed
        dist_cfg = distributed.DistributedConfig.from_args(args)
        if distributed.initialize(dist_cfg):
            print(f"[INFO] Joined distributed runtime: host "
                  f"{distributed.process_index()}/"
                  f"{distributed.process_count()}")
        variant = _load_variant(args.engine_variant)
        config = _workflow_config(args, variant)
        profile_dir = getattr(args, "profile_dir", None) \
            or os.environ.get("PIO_PROFILE_DIR") or None
        metrics.install_jit_compile_listener()
        # one trace root over the whole train pass: the DASE stage
        # spans (dase.read/prepare/train/eval) nest under it, and a
        # --trace-dir exports the tree next to the jax.profiler capture
        with profile_trace(profile_dir), \
                trace_scope("pio.train",
                            attributes={"variant": args.engine_variant},
                            slow_exempt=True), \
                _train_progress_scope():
            instance_id = create_workflow(config, variant=variant)
    except TrainingInterruption as e:
        print(f"[INFO] Training interrupted: {e}")
        return 0
    except Exception as e:
        print(f"[ERROR] Training failed: {e}", file=sys.stderr)
        return 1
    if instance_id is None:
        if not distributed.is_primary_host():
            print("[INFO] Secondary host: training complete; persistence "
                  "done by host 0.")
        else:
            print("[INFO] Training interrupted by a stop-after flag.")
        return 0
    print(f"[INFO] JIT compiles: {int(metrics.JIT_COMPILES.value())} "
          f"events, {metrics.JIT_COMPILE_SECONDS.value():.3f} s")
    print(f"[INFO] Training completed. Engine instance ID: {instance_id}")
    return 0


def _cmd_eval_grid(args) -> int:
    """``pio eval --grid grid.json``: the vmapped tuning lane. The grid
    file's ALSParams configs are validated LOUDLY (every unknown or
    non-sweepable field named, before any device work), the app's rate
    events are read once and leave-last-out split, and ONE device
    program trains every config against the shared bucketed tables —
    sized to the HBM budget, diverged configs masked out. Writes the
    leaderboard artifact (metric per config; winner pinned with its
    full EngineParams) to ``--grid-out``."""
    import numpy as np

    from predictionio_tpu.ops import als as _als
    from predictionio_tpu.ops import tuning as ops_tuning
    from predictionio_tpu.workflow import tuning as wf_tuning

    try:
        with open(args.grid, "r", encoding="utf-8") as f:
            spec = json.load(f)
    except (OSError, ValueError) as e:
        print(f"[ERROR] cannot read grid file {args.grid}: {e}",
              file=sys.stderr)
        return 1
    if not isinstance(spec, dict):
        print(f"[ERROR] {args.grid}: grid file must be a JSON object",
              file=sys.stderr)
        return 1
    unknown = sorted(set(spec) - {"base", "configs", "data"})
    if unknown:
        for key in unknown:
            print(f"[ERROR] {args.grid}: unknown section {key!r} "
                  "(expected: base, configs, data)", file=sys.stderr)
        return 1
    try:
        grid = ops_tuning.grid_from_spec(
            {k: spec[k] for k in ("base", "configs") if k in spec})
    except ops_tuning.GridConfigError as e:
        # the per-field loudness contract: one [ERROR] line per problem
        for line in str(e).splitlines():
            print(f"[ERROR] {args.grid}: {line.strip()}",
                  file=sys.stderr)
        return 1
    data_spec = spec.get("data") or {}
    app_name = data_spec.get("appName") or data_spec.get("app_name")
    if not app_name:
        print(f"[ERROR] {args.grid}: missing data.appName (the event "
              "app to tune against)", file=sys.stderr)
        return 1
    event_names = list(data_spec.get("eventNames", ["rate"]))

    from predictionio_tpu.data.store import PEventStore

    try:
        batch = PEventStore.find_columnar(
            app_name=app_name,
            channel_name=data_spec.get("channelName"),
            entity_type="user", event_names=event_names,
            target_entity_type="item", value_property="rating",
            default_value=1.0)
    except Exception as e:
        print(f"[ERROR] cannot read events for app {app_name!r}: {e}",
              file=sys.stderr)
        return 1
    if len(batch.entity_ids) == 0:
        print(f"[ERROR] app {app_name!r} has no "
              f"{'/'.join(event_names)} events to tune on",
              file=sys.stderr)
        return 1
    users, rows = np.unique(np.asarray(batch.entity_ids),
                            return_inverse=True)
    items, cols = np.unique(np.asarray(batch.target_ids),
                            return_inverse=True)
    vals = np.asarray(batch.values, dtype=np.float32)

    # leave-last-out holdout in stream order (the sliding-eval
    # protocol): each user's LAST interaction is the test target
    held: Dict[int, set] = {}
    train_mask = np.ones(len(rows), dtype=bool)
    order = np.argsort(rows, kind="stable")
    start = 0
    while start < len(order):
        end = start
        while end < len(order) and rows[order[end]] == rows[order[start]]:
            end += 1
        if end - start >= 2:
            last = order[end - 1]
            train_mask[last] = False
            held[int(rows[last])] = {int(cols[last])}
        start = end
    tr, tc, tv = rows[train_mask], cols[train_mask], vals[train_mask]
    if not len(tr):
        print(f"[ERROR] app {app_name!r}: no training interactions "
              "left after the leave-last-out split", file=sys.stderr)
        return 1

    user_side, item_side = _als.bucket_ratings_pair(
        tr, tc, tv, len(users), len(items))
    user_side, item_side = user_side.to_device(), item_side.to_device()

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.templates.recommendation.engine import (
        DataSourceParams,
    )

    ep_base = EngineParams(
        data_source_params=("", DataSourceParams(
            app_name=str(app_name), event_names=tuple(event_names))))
    print(f"[INFO] grid eval: {grid.k} configs x "
          f"{int(grid.base.num_iterations)} iterations on "
          f"{len(tr)} train / {len(held)} held-out interactions "
          f"({len(users)} users, {len(items)} items)")
    from predictionio_tpu.data.storage.localfs import atomic_write_bytes

    out = args.grid_out

    def stream_partial(partial_board) -> None:
        # a killed sweep leaves the latest completed sub-batch's board
        # on disk — atomic, so readers never see a torn artifact
        atomic_write_bytes(
            out, json.dumps(partial_board, indent=2).encode("utf-8"))
        print(f"[INFO] partial leaderboard "
              f"({partial_board.get('batchesCompleted')}/"
              f"{len(partial_board.get('batches') or [])} "
              f"sub-batches) -> {out}")

    board = wf_tuning.run_grid(
        user_side, item_side, grid, train_rows=tr, train_cols=tc,
        held=held, topk=int(getattr(args, "topk", 10) or 10),
        engine_params_base=ep_base, on_partial=stream_partial)

    atomic_write_bytes(out, json.dumps(board, indent=2).encode("utf-8"))
    diverged = [r["config"] for r in board["rows"] if r["diverged"]]
    if diverged:
        print(f"[WARN] diverged configs masked out: {diverged}")
    w = board["winner"]
    if w is None:
        print("[ERROR] every config diverged — no winner",
              file=sys.stderr)
        return 1
    print(f"[INFO] winner: config {w['config']} {w['params']} "
          f"{board['metricName']}={w['metric']:.4f} "
          f"(ndcg@{board['k']}={w['ndcgAtK']:.4f}); leaderboard -> {out}")
    return 0


def cmd_eval(args) -> int:
    """Console eval (Console.scala:750-757): evaluation class + optional
    params-generator class -> run_evaluation. With ``--grid``, the
    vmapped multi-config tuning lane instead (:func:`_cmd_eval_grid`)."""
    if getattr(args, "grid", None):
        return _cmd_eval_grid(args)
    if not args.evaluation:
        print("[ERROR] eval needs an Evaluation class "
              "(module:callable) or --grid grid.json", file=sys.stderr)
        return 1
    from predictionio_tpu.controller.evaluation import (
        Evaluation, EngineParamsGenerator)
    from predictionio_tpu.data.storage.base import EvaluationInstance
    from predictionio_tpu.workflow import core_workflow, run_evaluation
    from predictionio_tpu.workflow.create_workflow import pio_env_vars

    try:
        evaluation = core_workflow.load_engine_factory(args.evaluation)()
        if not isinstance(evaluation, Evaluation):
            raise TypeError(f"{args.evaluation} is not an Evaluation")
        if args.engine_params_generator:
            generator = core_workflow.load_engine_factory(
                args.engine_params_generator)()
            if not isinstance(generator, EngineParamsGenerator):
                raise TypeError(f"{args.engine_params_generator} is not an "
                                "EngineParamsGenerator")
            params_list = generator.engine_params_list
        elif isinstance(evaluation, EngineParamsGenerator):
            params_list = evaluation.engine_params_list
        else:
            raise ValueError(
                "no engine params: pass an EngineParamsGenerator class or "
                "make the Evaluation also an EngineParamsGenerator")
    except Exception as e:
        print(f"[ERROR] {e}", file=sys.stderr)
        return 1

    now = _dt.datetime.now(tz=_dt.timezone.utc)
    instance = EvaluationInstance(
        id="", status="INIT", start_time=now, end_time=now,
        evaluation_class=args.evaluation,
        engine_params_generator_class=args.engine_params_generator or "",
        batch=getattr(args, "batch", "") or "",
        env=pio_env_vars(),
    )
    try:
        result = run_evaluation(
            evaluation.engine, params_list, instance, evaluation.evaluator,
            evaluation=evaluation)
    except Exception as e:
        print(f"[ERROR] Evaluation failed: {e}", file=sys.stderr)
        return 1
    print(f"[INFO] {result.to_one_liner()}")
    return 0


def cmd_deploy(args) -> int:
    """Console deploy (Console.scala:844-878): serve the given or latest
    COMPLETED engine instance until interrupted."""
    from predictionio_tpu.workflow import QueryServer, ServerConfig

    _apply_metrics_flag(args)
    _apply_tracing_flags(args)
    _apply_precision_flags(args)
    foldin = getattr(args, "foldin", "off") == "on"
    # no env write here: QueryServer.deploy() sets PIO_FOLDIN from
    # ServerConfig(foldin=True) before the model loads, and setting it
    # earlier would make deploy() capture "1" as the prior value —
    # defeating its own restore on stop()/failed deploy
    if args.feedback and not args.accesskey:
        # CreateServer.scala:452-455: feedback requires an access key
        print("[ERROR] Feedback loop cannot be enabled because accessKey "
              "is empty. Pass --accesskey.", file=sys.stderr)
        return 1
    variant_id, variant_version = "default", "default"
    if os.path.exists(args.engine_variant):
        variant = _load_variant(args.engine_variant)
        variant_id = variant.get("id", "default")
        variant_version = variant.get("version", "default")
    config = ServerConfig(
        engine_instance_id=args.engine_instance_id,
        engine_id=getattr(args, "engine_id", None) or variant_id,
        engine_version=(getattr(args, "engine_version", None)
                        or variant_version),
        engine_variant=args.engine_variant,
        ip=args.ip,
        port=args.port,
        feedback=args.feedback,
        event_server_ip=args.event_server_ip,
        event_server_port=args.event_server_port,
        access_key=args.accesskey,
        server_config_path=getattr(args, "server_config", None),
        foldin=foldin,
        slo_config=getattr(args, "slo_config", None),
    )
    fleet_n = int(getattr(args, "fleet", 1) or 1)
    try:
        if fleet_n > 1:
            from predictionio_tpu.fleet.balancer import QueryFleet

            server = QueryFleet(config, replicas=fleet_n).start()
        else:
            server = QueryServer(config).start()
    except Exception as e:
        print(f"[ERROR] Deploy failed: {e}", file=sys.stderr)
        return 1
    host, port = server.address
    if fleet_n > 1:
        print(f"[INFO] Engine is deployed on a {fleet_n}-replica fleet. "
              f"Engine API is live at {server.scheme}://{host}:{port}.")
    else:
        print(f"[INFO] Engine is deployed and running. Engine API is live "
              f"at {server.scheme}://{host}:{port}.")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    return 0


def cmd_batchpredict(args) -> int:
    """Bulk offline scoring (the later releases' ``pio batchpredict``):
    queries from a JSONL file or synthesized from the event store, run
    through the full DASE serve path in restartable device-shaped
    chunks. See predictionio_tpu/batch/predict.py."""
    from predictionio_tpu.batch import (
        BatchPredictConfig,
        run_batch_predict,
        run_smoke,
    )

    _apply_metrics_flag(args)
    _apply_tracing_flags(args)
    _apply_precision_flags(args)
    if args.smoke:
        return run_smoke()
    if not args.output:
        print("[ERROR] --output is required (the shard/manifest "
              "directory).", file=sys.stderr)
        return 1
    try:
        base = json.loads(args.synthesize_base or "{}")
        if not isinstance(base, dict):
            raise ValueError("--synthesize-base must be a JSON object")
        variant_id, variant_version = "default", "default"
        if os.path.exists(args.engine_variant):
            variant = _load_variant(args.engine_variant)
            variant_id = variant.get("id", "default")
            variant_version = variant.get("version", "default")
        config = BatchPredictConfig(
            output_dir=args.output,
            engine_instance_id=args.engine_instance_id,
            engine_id=getattr(args, "engine_id", None) or variant_id,
            engine_version=(getattr(args, "engine_version", None)
                            or variant_version),
            engine_variant=args.engine_variant,
            input_path=args.input,
            synthesize_app=args.synthesize_app,
            synthesize_entity_type=args.synthesize_entity_type,
            synthesize_field=args.synthesize_field,
            synthesize_base=base,
            synthesize_channel=args.channel,
            chunk_size=args.chunk_size,
            query_partitions=args.query_partitions,
            format=args.format,
            batch=getattr(args, "batch", "") or "",
        )
        summary = run_batch_predict(config)
    except Exception as e:
        print(f"[ERROR] Batch predict failed: {e}", file=sys.stderr)
        return 1
    print(f"[INFO] Batch predict completed: {summary['queries']} queries "
          f"in {summary['chunks']} chunks "
          f"({summary['chunksScored']} scored, "
          f"{summary['chunksSkipped']} resumed) -> "
          f"{summary['outputDir']} "
          f"[{summary['queriesPerSec']} q/s scoring]")
    return 0


def cmd_undeploy(args) -> int:
    """Console undeploy (Console.scala:880-890): stop a running server.
    Probes HTTP first, then HTTPS, so it stops servers deployed with a
    TLS server.json without needing to know which scheme is live."""
    from predictionio_tpu.workflow import undeploy

    if undeploy(args.ip, args.port) \
            or undeploy(args.ip, args.port, scheme="https"):
        print("[INFO] Undeployed.")
        return 0
    print(f"[ERROR] Nothing at {args.ip}:{args.port} responded to /stop.",
          file=sys.stderr)
    return 1


def cmd_eventserver(args) -> int:
    """Console eventserver (Console.scala:741-745)."""
    import os

    from predictionio_tpu.data.api import EventServer, EventServerConfig

    _apply_metrics_flag(args)
    _apply_tracing_flags(args)  # $PIO_TRACE_DIR exports this side too
    service_key = getattr(args, "service_key", None) \
        or os.environ.get("PIO_EVENTSERVER_SERVICE_KEY") or None
    server = EventServer(EventServerConfig(
        ip=args.ip, port=args.port, stats=args.stats,
        service_key=service_key,
        server_config_path=getattr(args, "server_config", None))).start()
    host, port = server.address
    print(f"[INFO] Event Server is ready at {server.scheme}://{host}:{port}.")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    return 0


def cmd_adminserver(args) -> int:
    """Console adminserver (Console.scala:747-751)."""
    from predictionio_tpu.tools.admin_server import AdminServer, AdminServerConfig

    server = AdminServer(AdminServerConfig(ip=args.ip, port=args.port))
    print(f"[INFO] Admin Server is ready at http://{args.ip}:{args.port}.")
    server.serve_forever()
    return 0


def cmd_dashboard(args) -> int:
    """Console dashboard (Console.scala:753-757)."""
    from predictionio_tpu.common import ServerConfig
    from predictionio_tpu.tools.dashboard import Dashboard, DashboardConfig

    server_config = ServerConfig.load(args.server_config) \
        if args.server_config else ServerConfig.load()
    server = Dashboard(DashboardConfig(ip=args.ip, port=args.port,
                                       server_config=server_config))
    scheme = "https" if server_config.ssl_certfile else "http"
    print(f"[INFO] Dashboard is ready at {scheme}://{args.ip}:{args.port}.")
    server.serve_forever()
    return 0
