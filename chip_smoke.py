#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip: load -> ``pio train`` -> ``pio deploy --foldin on`` -> queries ->
online fold-in of a new user -> ``POST /stop``, at the one shape the repo
names as its target (BASELINE.json: 138,000 users x 27,000 items x
20,000,000 ``rate`` events, rank 64 — MovieLens-20M-sized, drawn from
``--seed`` with the power laws of :func:`synthetic_events`).

Every stage that touches the device is a child process running the real
CLI (``python -m predictionio_tpu.tools.console ...``), one at a time, so
the chip has one owner at any moment. THIS process never imports jax: it
writes the events, reads the persisted factors back from the model store,
and checks the served answers against a plain numpy float32 top-10.

Exit code 0 only if every stage passed; stdout then holds two JSON
lines: the report (what each stage found), and LAST the verdict, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reported it. Any exception, any platform but ``tpu``, any AOT
``fallback`` on the serving ladder, any ``miss_jit`` dispatch or any
failed query (a 503 shed included) before or after the fold-in grows
the store, any skipped stage ends the run non-zero and prints no
result line.

``--toy`` runs the same stages at toy sizes on whatever platform jax
finds (CPU in the test suite) so the wiring is debugged off the chip;
both its lines are stamped ``"toy": true`` and are never evidence for the
chip.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# the published shape (BASELINE.json).
# Rank and the two table sizes are never cut; --events may be, never
# below ``min_events``, and every cut is printed under "reduced".
FULL = dict(n_users=138_000, n_items=27_000, events=20_000_000,
            min_events=2_000_000, rank=64, iterations=3,
            block=1_000_000, new_user_items=24)
TOY = dict(n_users=300, n_items=200, events=9_000, min_events=1,
           rank=8, iterations=3, block=4_000, new_user_items=6)

N_QUERIES = 32          # per phase: half one at a time, half concurrent
TOP_N = 10
# bf16 store, fp32 accumulate: each stored factor carries 8 mantissa
# bits (relative rounding 2^-9 ~ 2e-3); a 64-term dot product of mixed
# sign can lose a few of those to cancellation, so scores agree with the
# fp32 oracle to ~1e-2 relative and near-ties may swap one rank
MIN_SHARED = 9
TOP_SCORE_RTOL = 2e-2
APP = "SmokeApp"
NEW_USER = "smoke-new-user"
STAGE_TIMEOUT = 900.0   # train; deploy until it prints its address
FOLDIN_TIMEOUT = 300.0  # event -> servable, the grown store's ladder
#                         compile included
# the server's flight recorder must hold every dispatch of the run (the
# background readers of the fold-in stage send thousands)
FLIGHT_RING = 1 << 18


class StageFailed(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise StageFailed(what)


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def _tail(path: str, n: int = 40) -> str:
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def _kill_group(proc: subprocess.Popen) -> None:
    """Stop a child and everything it started (own session/group)."""
    if proc.poll() is not None:
        return
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            return
        try:
            proc.wait(timeout=10)
            return
        except subprocess.TimeoutExpired:
            continue


def run_child(name: str, argv: List[str], env: Dict[str, str], cwd: str,
              logdir: str, timeout: float) -> str:
    """Run one child to completion; returns its stdout. Non-zero exit
    or timeout fails the stage with the tail of its stderr."""
    err_path = os.path.join(logdir, f"{name}.stderr.log")
    out_path = os.path.join(logdir, f"{name}.stdout.log")
    with open(out_path, "w") as out, open(err_path, "w") as err:
        proc = subprocess.Popen(argv, env=env, cwd=cwd, stdout=out,
                                stderr=err, start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            raise StageFailed(
                f"{name}: no exit within {timeout:.0f}s\n{_tail(err_path)}")
        finally:
            _kill_group(proc)
    with open(out_path, "r", encoding="utf-8", errors="replace") as f:
        stdout = f.read()
    if rc != 0:
        raise StageFailed(f"{name}: exit code {rc}\n{stdout[-2000:]}\n"
                          f"{_tail(err_path)}")
    return stdout


def pio(*verb: str) -> List[str]:
    return [sys.executable, "-u", "-m", "predictionio_tpu.tools.console",
            *verb]


def http_json(method: str, url: str, body: Any = None,
              timeout: float = 60.0) -> Any:
    data = None if body is None else json.dumps(body).encode("utf-8")
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def http_text(url: str, timeout: float = 60.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode("utf-8")


def metric_value(exposition: str, name: str) -> float:
    """One un-labelled sample out of a Prometheus text exposition."""
    for line in exposition.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise StageFailed(f"/metrics has no sample {name}")


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def synthetic_events(n_users: int, n_items: int, n_events: int, seed: int,
                     chunk: int):
    """Power-law synthetic ratings (item popularity
    rank^-0.8, user activity rank^-0.6, ratings 1..5), drawn chunk by
    chunk from one generator so the stream never has to exist whole."""
    rng = np.random.default_rng(seed)
    item_p = 1.0 / np.arange(1, n_items + 1) ** 0.8
    item_p /= item_p.sum()
    user_p = 1.0 / np.arange(1, n_users + 1) ** 0.6
    user_p /= user_p.sum()
    for off in range(0, n_events, chunk):
        m = min(chunk, n_events - off)
        yield (rng.choice(n_users, size=m, p=user_p),
               rng.choice(n_items, size=m, p=item_p),
               rng.integers(1, 6, size=m))


def event_lines(user_ids, items, ratings) -> List[str]:
    """``rate`` events as wire-format JSON lines (the event API's spelling);
    ``user_ids`` are entity ids, ``items`` item numbers."""
    return [f'{{"event":"rate","entityType":"user","entityId":"{u}",'
            f'"targetEntityType":"item","targetEntityId":"i{i}",'
            f'"properties":{{"rating":{r}}},'
            f'"eventTime":"2020-01-01T00:00:00+00:00"}}'
            for u, i, r in zip(user_ids, items.tolist(),
                               ratings.tolist())]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, args: argparse.Namespace):
        self.args = args
        self.toy = bool(args.toy)
        self.cfg = dict(TOY if self.toy else FULL)
        self.reduced: List[str] = []
        if args.events is not None:
            check(args.events >= self.cfg["min_events"],
                  f"--events {args.events} is below the floor of "
                  f"{self.cfg['min_events']}")
            if args.events != self.cfg["events"]:
                self.reduced.append(
                    f"events {self.cfg['events']} -> {args.events} "
                    "(--events)")
                self.cfg["events"] = int(args.events)
        self.out = os.path.abspath(args.out)
        self.work = os.path.abspath(args.work)
        self.logs = os.path.join(self.out, "logs")
        self.engine_dir = os.path.join(self.work, "engine")
        self.ckpt_dir = os.path.join(self.work, "checkpoints")
        self.stage_sec: Dict[str, float] = {}
        self.report: Dict[str, Any] = {}
        self.server: Optional[subprocess.Popen] = None
        self.server_lines: List[str] = []
        self.base_url = ""
        self.answers: Dict[str, Dict[str, Any]] = {}

        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "") \
            if env.get("PYTHONPATH") else REPO
        # a missing chip must be an init error in every child, not jax's
        # silent CPU choice. A caller that pinned a platform keeps it —
        # the probe then names it and the run ends there
        env["JAX_PLATFORMS"] = os.environ.get("JAX_PLATFORMS") or \
            ("cpu" if self.toy else "tpu")
        env.update({
            "PIO_STORAGE_SOURCES_META_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_META_PATH":
                os.path.join(self.work, "meta.db"),
            "PIO_STORAGE_SOURCES_EV_TYPE": "jsonlfs",
            "PIO_STORAGE_SOURCES_EV_PATH":
                os.path.join(self.work, "events"),
            "PIO_STORAGE_SOURCES_EV_PART_MAX_EVENTS":
                str(self.cfg["block"]),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "META",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "META",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        })
        self.env = env

    # -- plumbing ----------------------------------------------------------

    def stage(self, name: str, fn) -> None:
        log(f"stage {name} ...")
        t0 = time.monotonic()
        fn()
        self.stage_sec[name] = round(time.monotonic() - t0, 2)
        log(f"stage {name} passed in {self.stage_sec[name]}s")

    def child(self, name: str, argv: List[str], timeout: float,
              cwd: Optional[str] = None, env=None) -> str:
        return run_child(name, argv, env or self.env, cwd or self.work,
                         self.logs, timeout)

    def cache_names(self) -> set:
        from predictionio_tpu.utils import compile_cache

        return compile_cache.entry_names(self.cache_dir)

    def cache_entries(self) -> int:
        return len(self.cache_names())

    # -- stages ------------------------------------------------------------

    def probe(self) -> None:
        """One child imports jax, places the compile cache the way every
        ``pio`` verb does, and reports what it found."""
        code = (
            "import json, jax\n"
            "from predictionio_tpu.utils import compile_cache\n"
            "d = compile_cache.configure()\n"
            "ds = jax.devices()\n"
            "print(json.dumps({'platform': ds[0].platform,"
            " 'kind': ds[0].device_kind, 'count': len(ds),"
            " 'cacheDir': d, 'jax': jax.__version__}))\n")
        out = self.child("probe", [sys.executable, "-c", code], 300)
        info = json.loads(out.strip().splitlines()[-1])
        self.device = {"platform": info["platform"], "kind": info["kind"],
                       "count": int(info["count"])}
        self.cache_dir = info["cacheDir"]
        self.report["jax"] = info["jax"]
        log(f"device: {self.device}; compile cache at {self.cache_dir}")
        if not self.toy:
            check(self.device["platform"] == "tpu",
                  f"platform is {self.device['platform']!r}, not 'tpu': "
                  "chip_smoke.py measures nothing without the chip "
                  "(--toy debugs the wiring on CPU)")
        self.on_tpu = self.device["platform"] == "tpu"
        # what a default deploy serves with on each platform
        self.want_precision = "bf16" if self.on_tpu else "fp32"
        self.want_kernel = "fused" if self.on_tpu else "xla"
        if self.args.chips is not None:
            check(self.device["count"] == self.args.chips,
                  f"expected {self.args.chips} device(s), jax found "
                  f"{self.device['count']}")

    def load(self) -> None:
        cfg = self.cfg
        out = self.child("app_new", pio("app", "new", APP), 120)
        app_id = next(int(ln.split("ID:")[1]) for ln in out.splitlines()
                      if "ID:" in ln)
        # bulk set-up through the store's own bulk lane
        # (append_raw_lines, what `pio import` uses): 20M
        # events are data, not traffic
        from predictionio_tpu.data.storage.jsonlfs import JsonlFsLEvents

        le = JsonlFsLEvents({
            "path": self.env["PIO_STORAGE_SOURCES_EV_PATH"],
            "part_max_events": cfg["block"]})
        le.init(app_id)
        keys = []
        t0 = time.monotonic()
        for users, items, ratings in synthetic_events(
                cfg["n_users"], cfg["n_items"], cfg["events"],
                self.args.seed, cfg["block"]):
            le.append_raw_lines(
                event_lines(map("u{}".format, users.tolist()), items,
                            ratings), app_id)
            keys.append(users.astype(np.int64) * cfg["n_items"] + items)
        self.report["eventWriteSec"] = round(time.monotonic() - t0, 2)
        # the distinct (user, item) pairs: what "coverage 1.0" counts
        # and where the oracle's seen sets come from
        self.pairs = np.unique(np.concatenate(keys))
        self.known_users = np.unique(self.pairs // cfg["n_items"])
        self.report["events"] = int(cfg["events"])
        self.report["uniquePairs"] = int(len(self.pairs))
        self.child("template_get",
                   pio("template", "get", "recommendation",
                       self.engine_dir), 120)
        variant_path = os.path.join(self.engine_dir, "engine.json")
        with open(variant_path, "r", encoding="utf-8") as f:
            variant = json.load(f)
        # README "At scale": streamed bounded blocks + length buckets
        variant["datasource"]["params"].update(
            appName=APP, streamingBlockSize=cfg["block"])
        variant["preparator"] = {"params": {"bucketed": True}}
        variant["algorithms"][0]["params"].update(
            rank=cfg["rank"], numIterations=cfg["iterations"])
        with open(variant_path, "w", encoding="utf-8") as f:
            json.dump(variant, f, indent=2)

    def seen_of(self, user: int) -> np.ndarray:
        n_items = self.cfg["n_items"]
        lo = np.searchsorted(self.pairs, user * n_items)
        hi = np.searchsorted(self.pairs, (user + 1) * n_items)
        return self.pairs[lo:hi] - user * n_items

    def train(self) -> None:
        before = self.cache_entries()
        # --checkpoint-every 1: the chunked loop samples the on-device
        # objective after every iteration into the run log
        out = self.child(
            "train",
            pio("train", "--checkpoint-dir", self.ckpt_dir,
                "--checkpoint-every", "1"),
            STAGE_TIMEOUT, cwd=self.engine_dir)
        self.instance_id = next(
            ln.rsplit(":", 1)[1].strip() for ln in out.splitlines()
            if "Engine instance ID:" in ln)
        compile_line = next(ln for ln in out.splitlines()
                            if "JIT compiles:" in ln)
        self.report["train"] = train = {
            "compileSeconds": float(compile_line.split(",")[1].split()[0]),
            "cacheEntriesBefore": before,
            "cacheEntriesAfter": self.cache_entries(),
        }
        runs = os.path.join(self.ckpt_dir, "runs")
        (run_file,) = [os.path.join(runs, n) for n in os.listdir(runs)]
        header, samples = None, []
        with open(run_file, "r", encoding="utf-8") as f:
            for line in f:
                entry = json.loads(line)
                if entry["type"] == "header":
                    header = entry
                else:
                    samples.append(entry)
        context = header["context"]
        losses = [s["loss"]["total"] for s in samples]
        train.update(
            solver=context["solver"], precision=context["precision"],
            trainedPairs=int(context["trainedPairs"]),
            devices=int(context["devices"]), losses=losses,
            deviceSeconds=[s["deviceSeconds"] for s in samples],
            hbmBytesInUsePerDevice=samples[-1]["hbmBytesInUsePerDevice"])
        check(len(samples) == self.cfg["iterations"],
              f"run log holds {len(samples)} samples, expected "
              f"{self.cfg['iterations']}")
        check(all(np.isfinite(losses)), f"non-finite objective: {losses}")
        check(losses[-1] < losses[0],
              f"objective did not fall: {losses}")
        check(train["trainedPairs"] == len(self.pairs),
              f"coverage {train['trainedPairs']}/{len(self.pairs)} "
              "unique pairs is not 1.0")
        check(train["devices"] == self.device["count"],
              f"trained over {train['devices']} device(s), jax found "
              f"{self.device['count']}")
        check(context["nUsers"] == len(self.known_users)
              and context["nItems"] <= self.cfg["n_items"],
              f"trained table {context['nUsers']} x {context['nItems']}")
        if self.on_tpu:
            # rank 64 on one chip takes the Pallas kernel; the sharded
            # trainer of several chips keeps lanes (ops/als.py,
            # _resolve_spd_solver: this process never imports jax)
            want = "pallas" if self.device["count"] == 1 else "lanes"
            check(train["solver"] == want,
                  f"solver {train['solver']!r}, expected {want!r} on "
                  f"{self.device['count']} tpu device(s)")
            per_dev = train["hbmBytesInUsePerDevice"]
            check(per_dev is not None
                  and len(per_dev) == self.device["count"]
                  and all(b > 0 for b in per_dev),
                  f"not every device holds training state: {per_dev}")
        self.load_model()

    def load_model(self) -> None:
        """The persisted factors, read back from the model store by this
        (jax-free) process — the oracle's only input besides the seed."""
        for k, v in self.env.items():
            if k.startswith("PIO_STORAGE_"):
                os.environ[k] = v
        from predictionio_tpu.data import storage
        from predictionio_tpu.workflow import core_workflow

        blob = storage.get_model_data_models().get(self.instance_id)
        check(blob is not None, "no persisted model for the trained "
                                f"instance {self.instance_id}")
        (model,) = core_workflow.deserialize_models(blob.models)
        self.X = np.asarray(model.user_factors, dtype=np.float32)
        self.Y = np.asarray(model.item_factors, dtype=np.float32)
        self.user_index = model.user_map
        self.item_index = model.item_map
        check(self.X.shape == (len(self.known_users), self.cfg["rank"]),
              f"user factors {self.X.shape}")
        check(self.Y.shape[1] == self.cfg["rank"],
              f"item factors {self.Y.shape}")
        check(bool(np.isfinite(self.X).all() and np.isfinite(self.Y).all()),
              "non-finite factors")
        self.item_labels = self.item_index.decode(
            np.arange(self.Y.shape[0], dtype=np.int64))

    def oracle_topn(self, user_vec: np.ndarray,
                    seen_items: np.ndarray) -> Tuple[List[str], np.ndarray]:
        """Plain numpy float32: scores = Y @ x, seen masked, top-N."""
        scores = self.Y @ user_vec.astype(np.float32)
        seen_labels = {f"i{i}" for i in seen_items.tolist()}
        seen_idx = [self.item_index[l] for l in seen_labels
                    if l in self.item_index]
        scores[np.asarray(seen_idx, dtype=np.int64)] = -np.inf
        top = np.argsort(-scores, kind="stable")[:TOP_N]
        return [str(self.item_labels[i]) for i in top], scores[top]

    # -- serving -----------------------------------------------------------

    def start_server(self, name: str) -> float:
        """``pio deploy`` as a long-lived child; returns seconds until
        it printed its address (model load + ladder compile)."""
        env = dict(self.env)
        if self.device["count"] > 1:
            env["PIO_SERVE_SHARDS"] = str(self.device["count"])
        env["PIO_FOLDIN_INTERVAL"] = "1.0"
        env["PIO_DEVICE_TELEMETRY_RING"] = str(FLIGHT_RING)
        err = open(os.path.join(self.logs, f"{name}.stderr.log"), "w")
        t0 = time.monotonic()
        self.server = subprocess.Popen(
            pio("deploy", "--port", "0", "--ip", "127.0.0.1",
                "--foldin", "on"),
            env=env, cwd=self.engine_dir, stdout=subprocess.PIPE,
            stderr=err, text=True, start_new_session=True)
        err.close()
        self.server_lines = []
        ready = threading.Event()

        def pump(proc: subprocess.Popen) -> None:
            for line in proc.stdout:
                self.server_lines.append(line)
                if "Engine API is live at" in line:
                    self.base_url = line.rsplit(" at ", 1)[1].strip() \
                        .rstrip(".")
                    ready.set()
            ready.set()  # EOF: the child died before it was ready

        threading.Thread(target=pump, args=(self.server,),
                         daemon=True).start()
        check(ready.wait(STAGE_TIMEOUT),
              f"{name}: not ready within {STAGE_TIMEOUT:.0f}s\n"
              + _tail(os.path.join(self.logs, f"{name}.stderr.log")))
        check(bool(self.base_url) and self.server.poll() is None,
              f"{name}: deploy exited {self.server.poll()}\n"
              + "".join(self.server_lines[-20:])
              + _tail(os.path.join(self.logs, f"{name}.stderr.log")))
        return round(time.monotonic() - t0, 2)

    def stop_server(self) -> None:
        proc, self.server = self.server, None
        http_json("POST", self.base_url + "/stop", {})
        self.base_url = ""
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            raise StageFailed("deploy did not exit within 120s of /stop")
        check(rc == 0, f"deploy exited {rc} after /stop")

    def query(self, user: str) -> List[Dict[str, Any]]:
        """One query. Any HTTP error raises — a 503 shed included: the
        smoke sends nothing a healthy server has reason to refuse."""
        resp = http_json("POST", self.base_url + "/queries.json",
                         {"user": user, "num": TOP_N})
        return resp["itemScores"]

    def check_answer(self, label: str, got: List[Dict[str, Any]],
                     want_items: List[str], want_scores: np.ndarray,
                     seen_items: np.ndarray, min_shared: int) -> int:
        items = [g["item"] for g in got]
        scores = np.asarray([g["score"] for g in got], dtype=np.float64)
        check(len(items) == TOP_N and len(set(items)) == TOP_N,
              f"{label}: expected {TOP_N} distinct items, got {got}; "
              f"oracle {want_items} {want_scores.tolist()}")
        check(bool(np.isfinite(scores).all()
                   and (np.diff(scores) <= 1e-6).all()),
              f"{label}: scores not finite descending: {scores}")
        seen_labels = {f"i{i}" for i in seen_items.tolist()}
        check(not seen_labels & set(items),
              f"{label}: seen items returned: "
              f"{sorted(seen_labels & set(items))}")
        shared = len(set(items) & set(want_items))
        check(shared >= min_shared,
              f"{label}: {shared}/{TOP_N} items shared with the numpy "
              f"oracle (need {min_shared}): got {items}, want "
              f"{want_items}")
        rel = abs(scores[0] - want_scores[0]) / abs(want_scores[0])
        check(rel <= TOP_SCORE_RTOL,
              f"{label}: top score {scores[0]} vs oracle "
              f"{want_scores[0]} (rel {rel:.3g} > {TOP_SCORE_RTOL})")
        return shared

    def query_round(self, label: str, users: np.ndarray) -> Dict[str, Any]:
        """len(users) queries: the first half one at a time, the rest
        at once (so the micro-batcher forms real batches); every answer
        is checked against the oracle."""
        names = [f"u{u}" for u in users.tolist()]
        half = len(names) // 2
        answers = [self.query(n) for n in names[:half]]
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=len(names) - half) as pool:
            answers += list(pool.map(self.query, names[half:]))
        # kept beside the result so two runs (one chip, four chips) can
        # be compared user by user
        self.answers[label] = dict(zip(names, answers))
        shared = []
        for u, name, got in zip(users.tolist(), names, answers):
            seen = self.seen_of(u)
            want_items, want_scores = self.oracle_topn(
                self.X[self.user_index[name]], seen)
            shared.append(self.check_answer(
                f"{label} {name} ({len(seen)} seen)", got, want_items,
                want_scores, seen, MIN_SHARED))
        return {"queries": len(names), "minShared": min(shared),
                "meanShared": round(float(np.mean(shared)), 2)}

    def pick_users(self, salt: int) -> np.ndarray:
        """Known users across the activity range, always including the
        heaviest (index 0: the longest seen list in the store)."""
        known = self.known_users
        rng = np.random.default_rng(self.args.seed + salt)
        picks = rng.choice(known[1:], size=N_QUERIES - 1, replace=False)
        return np.concatenate([known[:1], picks])

    def serve(self) -> None:
        before = self.cache_entries()
        ready_sec = self.start_server("deploy")
        self.report["serve"] = serve = {
            "readySec": ready_sec, "cacheEntriesBefore": before,
            "cacheEntriesAfter": self.cache_entries()}
        stats = http_json("GET", self.base_url + "/stats.json")
        (store_report,) = stats["device"]["stores"]
        store, ladder = store_report["store"], store_report["aotLadder"]
        serve.update(precision=store["precision"], kernel=store["kernel"],
                     storeBytes=store["totalBytes"],
                     seenBytes=store["components"]["seen"]["bytes"],
                     placement=store["placement"],
                     ladder=ladder["coverage"],
                     ladderBytes=ladder["memory"]["totalBytes"])
        check(store["precision"] == self.want_precision,
              f"store precision {store['precision']!r}, expected "
              f"{self.want_precision!r} on {self.device['platform']}")
        check(store["kernel"] == self.want_kernel,
              f"serving kernel {store['kernel']!r}, expected "
              f"{self.want_kernel!r} on {self.device['platform']}")
        check(store["nUsers"] == self.X.shape[0]
              and store["nItems"] == self.Y.shape[0],
              f"store serves {store['nUsers']} x {store['nItems']}")
        # the store is where jax says it is: one shard per device, on
        # the probed platform, every device carrying bytes
        placement = store["placement"]
        check(len(placement) == self.device["count"]
              and store.get("nShards", 1) == self.device["count"],
              f"item store spans {len(placement)} device(s) / "
              f"{store.get('nShards', 1)} shard(s), jax found "
              f"{self.device['count']}")
        check(all(p["platform"] == self.device["platform"]
                  and p["rows"] > 0 and p["bytes"] > 0 for p in placement),
              f"item store placement: {placement}")
        if self.on_tpu:
            check(all((p["bytesInUse"] or 0) >= p["bytes"]
                      for p in placement),
                  f"a device reports less HBM in use than its shard of "
                  f"the item store: {placement}")
        cov = ladder["coverage"]
        check(cov["planned"] > 0 and cov["compiled"] == cov["planned"]
              and cov["fallback"] == 0,
              f"AOT ladder coverage {cov}")
        serve["queries"] = self.query_round("serve", self.pick_users(1))
        serve["dispatch"] = self.check_dispatches(N_QUERIES)
        serve["compileSeconds"] = metric_value(
            http_text(self.base_url + "/metrics"),
            "pio_jit_compile_seconds_total")

    def check_dispatches(self, min_served: int) -> Dict[str, Any]:
        """The server's own flight recorder: every query dispatch since
        it started ran the expected program family, AOT-compiled, on
        the device — none compiled on the query path."""
        rep = http_json(
            "GET", self.base_url + f"/dispatches.json?limit={FLIGHT_RING}")
        check(rep["enabled"] and rep["evicted"] == 0,
              f"flight recorder {rep['enabled']=} {rep['evicted']=}")
        recs = [r for r in rep["dispatches"]
                if r["lane"] in ("user", "users")]
        served = sum(r["batch"] for r in recs)
        check(served >= min_served,
              f"flight recorder saw {served} queries, sent {min_served}")
        # "interpret" rides on Pallas dispatches only: False = the
        # Mosaic-compiled kernel ran, True = the interpreter did
        bad = [r for r in recs if not (
            r["kernel"] == self.want_kernel and r["aot"] == "hit"
            and r["precision"] == self.want_precision and r["deviceUs"] > 0
            and not r.get("interpret", False))]
        check(not bad, f"{len(bad)} dispatch(es) off the expected path, "
                       f"first: {bad[:1]}")
        stats = http_json("GET", self.base_url + "/stats.json")
        requests = stats["device"]["stores"][0]["aotLadder"]["requests"]
        check(requests["missJit"] == 0 and requests["hit"] > 0,
              f"AOT ladder requests {requests}")
        return {"lanes": {k: v["dispatches"]
                          for k, v in rep["summary"].items()},
                "batched": sum(1 for r in recs if r["batch"] > 1),
                "deviceUsP50": rep["summary"]["users"]["deviceUsP50"],
                "deviceUsP99": rep["summary"]["users"]["deviceUsP99"],
                "aot": requests}

    def foldin(self) -> None:
        """A brand-new user's events land in the live store through a
        second process; the deployed server folds the user in and
        serves them while other queries keep flowing — the first time
        the donating scatters run against concurrent readers."""
        cfg = self.cfg
        rated = np.arange(cfg["new_user_items"], dtype=np.int64) * 3
        path = os.path.join(self.work, "new_user.jsonl")
        with open(path, "w", encoding="utf-8") as f:
            f.write("\n".join(event_lines(
                [NEW_USER] * len(rated), rated,
                np.full(len(rated), 5))) + "\n")
        stop = threading.Event()
        background_errors: List[str] = []
        background_users = self.pick_users(2)

        def background() -> int:
            n = 0
            while not stop.is_set():
                try:
                    got = self.query(f"u{background_users[n % N_QUERIES]}")
                    if len(got) != TOP_N:
                        background_errors.append(f"{len(got)} items")
                except Exception as e:  # noqa: BLE001 - reported below
                    background_errors.append(repr(e))
                n += 1
            return n

        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            readers = [pool.submit(background) for _ in range(4)]
            try:
                t0 = time.monotonic()
                self.child("import_new_user",
                           pio("import", "--input", path, "--app-name", APP),
                           300, env={**self.env, "JAX_PLATFORMS": "cpu"})
                got: List[Dict[str, Any]] = []
                while not got:
                    if time.monotonic() - t0 > FOLDIN_TIMEOUT:
                        stats = http_json("GET",
                                          self.base_url + "/stats.json")
                        raise StageFailed(
                            f"new user not servable within "
                            f"{FOLDIN_TIMEOUT:.0f}s; fold-in "
                            f"stats {stats['foldin']}")
                    time.sleep(0.25)
                    got = self.query(NEW_USER)
                servable_sec = round(time.monotonic() - t0, 2)
            finally:
                stop.set()
            background_n = sum(r.result() for r in readers)
        check(not background_errors,
              f"{len(background_errors)} of {background_n} background "
              f"queries failed during the fold, first: "
              f"{background_errors[:1]}")
        # the numpy half-step the fold-in must reproduce (implicit ALS,
        # ops/als.py::_solve_rows): A = YtY + a*sum r y y^T + lam*I,
        # b = sum (1 + a*r) y
        with open(os.path.join(self.engine_dir, "engine.json")) as f:
            params = json.load(f)["algorithms"][0]["params"]
        lam, alpha = float(params["lambda"]), float(params.get("alpha", 1.0))
        idx = np.asarray([self.item_index[f"i{i}"] for i in rated.tolist()])
        Yr = self.Y[idx].astype(np.float64)
        Y64 = self.Y.astype(np.float64)
        r = np.full(len(idx), 5.0)
        A = Y64.T @ Y64 + (Yr * (alpha * r)[:, None]).T @ Yr \
            + lam * np.eye(self.Y.shape[1])
        b = ((1.0 + alpha * r)[:, None] * Yr).sum(axis=0)
        x = np.linalg.solve(A, b).astype(np.float32)
        want_items, want_scores = self.oracle_topn(x, rated)
        # one rank looser than the trained users: the served row was
        # SOLVED against the bf16 store, so store rounding enters twice
        shared = self.check_answer(f"foldin {NEW_USER}", got, want_items,
                                   want_scores, rated, MIN_SHARED - 1)
        after = self.query_round("after-foldin", self.pick_users(3))
        stats = http_json("GET", self.base_url + "/stats.json")
        fold = stats["foldin"]
        check(fold["foldErrors"] == 0 and fold["tailErrors"] == 0
              and fold["newUsers"] >= 1 and not fold["stale"],
              f"fold-in stats {fold}")
        capacity = stats["device"]["stores"][0]["store"]["userCapacity"]
        # the new user did not fit: the store grew (and was laddered
        # again before it was published), so the dispatches checked
        # below ran against both shapes
        check(capacity > self.X.shape[0],
              f"user capacity {capacity} after a new user joined "
              f"{self.X.shape[0]}")
        self.report["foldin"] = {
            "servableSec": servable_sec, "sharedWithOracle": shared,
            "backgroundQueries": background_n, "after": after,
            "folds": fold["folds"], "newUsers": fold["newUsers"],
            "lastSolveDeviceUs": fold["lastSolveDeviceUs"],
            "userCapacity": capacity,
            "dispatch": self.check_dispatches(
                2 * N_QUERIES + background_n),
        }

    def stop(self) -> None:
        self.stop_server()

    def redeploy(self) -> None:
        """A second ``pio deploy`` of the same model: its ladder must
        come out of the persistent cache (no new entries)."""
        names_before = self.cache_names()
        before = len(names_before)
        ready_sec = self.start_server("redeploy")
        compile_sec = metric_value(
            http_text(self.base_url + "/metrics"),
            "pio_jit_compile_seconds_total")
        got = self.query("u0")
        check(len(got) == TOP_N, f"redeploy served {len(got)} items")
        self.stop_server()
        new = sorted(self.cache_names() - names_before)
        after = before + len(new)
        self.report["redeploy"] = {
            "readySec": ready_sec, "compileSeconds": compile_sec,
            "cacheEntriesBefore": before, "cacheEntriesAfter": after}
        check(before > 0 and not new,
              f"second deploy added cache entries ({before} -> {after}): "
              "the ladder did not come from the persistent cache; new: "
              f"{[n[:40] for n in new]}")

    # -- driver ------------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        for d in (self.work, self.out):
            shutil.rmtree(d, ignore_errors=True)
        for d in (self.work, self.logs):
            os.makedirs(d)
        try:
            self.stage("probe", self.probe)
            self.stage("load", self.load)
            self.stage("train", self.train)
            self.stage("serve", self.serve)
            self.stage("foldin", self.foldin)
            self.stage("stop", self.stop)
            self.stage("redeploy", self.redeploy)
        finally:
            if self.server is not None:
                _kill_group(self.server)
            shutil.rmtree(self.work, ignore_errors=True)
        from predictionio_tpu import native

        native_loaded = {n: native.available(n)
                         for n in ("jsonl_codec", "ingest_kernels")}
        check(all(native_loaded.values()),
              f"native libraries not loaded: {native_loaded}")
        check("jax" not in sys.modules,
              "the smoke's parent imported jax")
        result = {"ok": True, "device": self.device}
        if self.toy:
            result["toy"] = True
        result.update(
            shape={k: self.cfg[k] for k in
                   ("n_users", "n_items", "events", "rank", "iterations")},
            seed=self.args.seed, reduced=self.reduced,
            stageSeconds=self.stage_sec, nativeLoaded=native_loaded,
            cacheDir=self.cache_dir, **self.report)
        return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--toy", action="store_true",
                    help="toy sizes on whatever platform jax finds; "
                         "debugs the wiring, proves nothing about the chip")
    ap.add_argument("--seed", type=int, default=13)
    ap.add_argument("--events", type=int, default=None,
                    help="cut the event count (never below 2,000,000 at "
                         "the full shape); recorded under 'reduced'")
    ap.add_argument("--chips", type=int, default=None,
                    help="fail unless jax finds exactly this many devices")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"),
                    help="logs and result.json land here")
    ap.add_argument("--work", default=os.path.join(REPO, ".chip_smoke_work"),
                    help="scratch (event store, model store, engine dir); "
                         "removed at the end")
    args = ap.parse_args(argv)
    smoke = None
    try:
        smoke = Smoke(args)
        result = smoke.run()
    except StageFailed as e:
        log(f"FAILED: {e}")
        if smoke is not None and smoke.stage_sec:
            # what the stages that did pass found, for the post-mortem
            # — beside the logs, never on stdout
            with open(os.path.join(args.out, "partial.json"), "w") as f:
                json.dump({"ok": False, "failed": str(e)[:2000],
                           "stageSeconds": smoke.stage_sec,
                           **smoke.report}, f)
        return 1
    line = json.dumps(result)
    with open(os.path.join(args.out, "result.json"), "w") as f:
        f.write(line + "\n")
    with open(os.path.join(args.out, "answers.json"), "w") as f:
        json.dump(smoke.answers, f)
    # two lines: what the stages found, then the verdict. The LAST line
    # holds "ok" and "device" and nothing else — whoever checks the run
    # parses that one and needs no knowledge of the report's fields
    verdict = {"ok": True, "device": result["device"]}
    if args.toy:
        verdict["toy"] = True
    print(line, flush=True)
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
