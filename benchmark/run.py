"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip: it builds the cell's state from the seeds,
starts what the cell needs in-process through the program's normal
entry points, warms the cell's own shapes, measures for ``--seconds``
and prints one JSON line last. ``--trace 0`` reports the end-to-end
metrics, ``--trace 1`` the per-layer ones (with a profiler trace of a
slice of the window behind the device numbers).

``--rehearse`` runs the same code at the toy sizes each file carries,
on the CPU, for tests: its line says ``platform: cpu`` and carries no
device metric. Without it a run that finds no TPU fails.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a driver gets: the cell, the run's arguments, a scratch
    directory (under ``$TMPDIR``, removed at exit) and a ``spans`` dict
    to which it adds the seconds of each set-up part."""

    def __init__(self, cell, args, workdir: str):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rehearse = bool(args.rehearse)
        self.workdir = workdir
        self.t_process_start = T_PROCESS_START
        self.spans: dict = {}


def prepare_process(cell, rehearse: bool) -> dict:
    """The configuration's environment (before the program is
    imported), the compile cache where the program puts it, the compile
    listener; returns the device block, or exits where no chip is."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.update({k: str(v) for k, v in
                       cell.config.get("env", {}).items()})

    from predictionio_tpu.utils import compile_cache, metrics

    if rehearse:
        import jax

        jax.config.update("jax_enable_compilation_cache", False)
    else:
        compile_cache.configure()
    metrics.install_jit_compile_listener()
    return _device_block(cell, rehearse)


def _device_block(cell, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if not rehearse:
        if platform != "tpu":
            raise SystemExit(
                f"benchmark: no accelerator (platform {platform!r}); a "
                "measurement never falls back to the CPU (--rehearse "
                "runs the toy sizes there)")
        if len(devs) < cell.chips:
            raise SystemExit(f"benchmark: cell {cell.name} needs "
                             f"{cell.chips} chips, found {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.harness import cell as cells

    cell = cells.load_cell(args.workload, rehearse=args.rehearse)
    if args.seconds is None:
        args.seconds = cells.load_benchmark()["run_seconds"]
    if args.rehearse:
        args.seconds = min(args.seconds,
                           float(cell.traffic.get("rehearse_seconds", 3)))
    device = prepare_process(cell, args.rehearse)

    workdir = tempfile.mkdtemp(prefix="pio-bench-")
    try:
        ctx = Context(cell, args, workdir)
        result = cells.load_driver(cell.traffic["kind"])(ctx)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    readers = result["readers"]
    readers["spans"] = ctx.spans
    readers["cell"] = cell
    readers["device"] = device
    memory = readers["after"]["memory"]
    readers["memory"] = memory
    device["memory_peak_bytes"] = memory and memory["peak"]
    correct = bool(result["correct"])
    out_metrics = {}
    if ctx.trace:
        trace = readers.get("trace")
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        for m in cell.per_layer:
            read = cells.load_layer_metric(m["name"])
            value = read(readers) if read is not None else None
            if value is not None:
                out_metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = result["end_to_end"].get(m["name"])
            if value is not None:
                out_metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    if result.get("compiles_in_window"):
        correct = False
        result.setdefault("why", []).append(
            f"{result['compiles_in_window']} compiles in the window")
    if result.get("why"):
        print("benchmark: not correct: " + "; ".join(result["why"][:8]),
              file=sys.stderr)
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": out_metrics,
            "device": device}
    if ctx.trace and readers.get("trace") is not None:
        line["breakdown"] = {
            "device_ops": readers["trace"]["device_ops"][:10],
            "idle_gaps": readers["trace"]["idle_gaps"][:10]}
    # details for PERF.md go to stderr; stdout ends with the one line
    print("benchmark: spans " + json.dumps(
        {k: float(f"{v:.4g}") for k, v in ctx.spans.items()}),
        file=sys.stderr)
    import jax

    print("benchmark: memory_stats " + json.dumps(
        jax.local_devices()[0].memory_stats() or {}), file=sys.stderr)
    if result.get("notes"):
        print("benchmark: notes " + json.dumps(result["notes"]),
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
