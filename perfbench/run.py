"""Run one benchmark cell once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell named in BENCHMARK.json from its configuration, traffic
and metric files, serves its traffic through the program's
``AsyncServingServer`` over a ``ServingEngine`` on one chip, or over a
``ShardedServingEngine`` with one shard per chip where the cell asks for
more (the configuration's ``max_batch`` and ``num_pages`` are then per
shard, and every chip holds a replica of the weights), measures for
``--seconds`` after set-up and warm-up, then checks the served tokens
against the configuration's plain reference. With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are reported;
with ``--trace 0``, the end-to-end ones. The last line of standard output
is one JSON object; without a TPU the run exits 1 and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import asyncio                                                 # noqa: E402
import gc                                                      # noqa: E402
import json                                                    # noqa: E402
import math                                                    # noqa: E402
import os                                                      # noqa: E402
import shutil                                                  # noqa: E402
import sys                                                     # noqa: E402
from pathlib import Path                                       # noqa: E402

# the TPU runtime otherwise writes its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]

from harness import spec                                       # noqa: E402
from harness.device import NoChip                              # noqa: E402

BAD_FINISH = ("rejected", "shed", "error", "deadline", "rate_limited",
              "timeout")


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             device: dict, log, t_start: float, root: Path = ROOT,
             control: bool = False) -> dict:
    """Serve one cell once; returns the result object (without printing).
    ``control`` also reads the float8 control on the same sample."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from harness import model as M, oracle
    from harness.device import PEAKS, memory_peak_bytes
    from harness.readings import Context, output_tok_s, p95
    from harness.serve import Run, compile_warmup, log as progress
    from harness.trace import read_xplane, reduce
    from harness.traffic import Traffic
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import EngineConfig, ServingEngine
    from repro.serving.sharded import ShardedServingEngine

    cfg, mix = cell.config, cell.traffic
    eng_cfg = EngineConfig(**cfg["engine"], shards=cell.chips)
    mesh = make_serving_mesh(cell.chips) if cell.chips > 1 else None
    traffic = Traffic(mix, seed, cfg["vocab_size"])
    if traffic.worst_case_tokens() > eng_cfg.max_len:
        raise ValueError(f"{cell.name}: a request can hold "
                         f"{traffic.worst_case_tokens()} tokens, more than "
                         f"max_len {eng_cfg.max_len}")
    split = {}
    progress(f"{cell.name}: weights")
    t = time.perf_counter()
    weights = M.make_weights(
        cfg, seed, None if mesh is None else NamedSharding(mesh, P()))
    jax.block_until_ready(weights)
    split["init_s"] = time.perf_counter() - t
    progress("engine")
    t = time.perf_counter()
    model = M.Model(M.program_config(cfg))
    params = M.program_params(cfg, weights, model)
    engine = (ServingEngine(model, params, eng_cfg) if mesh is None else
              ShardedServingEngine(model, params, eng_cfg, mesh=mesh))
    del params
    jax.block_until_ready(engine.caches)
    split["engine_s"] = time.perf_counter() - t

    trace_dir = None
    if trace:
        trace_dir = str(root / ".perfbench_cache" / "trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(engine, traffic, seconds, trace_dir)
    compiles = {}

    def on_window(edge):
        compiles[edge] = (log.compiles, log.traces)
    run.on_window = on_window
    workers = (int(mix.get("workers_per_slot", 0)) * eng_cfg.max_batch
               * cell.chips)
    t = time.perf_counter()
    asyncio.run(run.serve(compile_warmup, float(mix["warmup_s"]),
                          float(mix.get("drain_s", 0)), workers))
    split["warmup_s"] = run.t_w0 - t
    setup_s = run.t_w0 - t_start
    peak_bytes = memory_peak_bytes(cell.chips)
    n_compiled = compiles["w1"][0] - compiles["w0"][0]
    n_traced = compiles["w1"][1] - compiles["w0"][1]

    kind = device["kind"]
    ctx = Context(cfg=cfg, run=run, setup_s=setup_s, peak=PEAKS.get(kind))
    if trace:
        progress("reading the trace")
        ctx.reduced = reduce(read_xplane(trace_dir, cell.chips))
    wanted = cell.per_layer if trace else cell.end_to_end
    kind_dir = "metrics" if trace else "end_to_end"
    metrics = {}
    for m in wanted:
        value = spec.reader(kind_dir, m["name"])(ctx)
        if value is not None and math.isfinite(value):   # inf: unanswered
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    due = ctx.due_in_window()
    failed = [r for r in due if r.finish_reason in BAD_FINISH
              or (mix["loop"] == "open" and not r.times)]
    finished = [r for r in run.records
                if r.due < run.t_w1 and r.finish_reason == "length"]
    lateness = p95([1e3 * (r.sent - r.due) for r in due
                    if r.sent is not None])

    # the program's state goes before the reference runs
    run.engine = None
    del engine, model
    gc.collect()
    weights = M.one_replica(weights)
    progress(f"reference on {len(finished)} finished requests")
    t = time.perf_counter()
    ref = oracle.load_reference(spec.BENCH_DIR, cfg["architecture"])
    picked = oracle.sample(finished, seed)
    gap = oracle.widest_gap(ref, cfg, weights, picked)
    split["reference_s"] = time.perf_counter() - t
    control_gap = (oracle.widest_gap(ref, cfg, weights, picked, control=True)
                   if control else None)
    limit = cfg["check"]["widest_logit_gap"]
    checks = {
        "widest_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": len(failed), "limit": 0},
    }
    correct = (gap is not None and limit is not None and gap <= limit
               and not failed)

    dev = dict(device)
    dev["memory_peak_bytes"] = peak_bytes
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": len(failed), "metrics": metrics, "device": dev}
    if trace and ctx.reduced is not None:
        dev["busy_s"] = ctx.reduced.busy_ns * 1e-9
        dev["window_s"] = ctx.reduced.window_ns * 1e-9
        result["breakdown"] = {"device_ops": ctx.reduced.top_ops,
                               "idle_gaps": ctx.reduced.idle_by_span}
    result["checks"] = checks
    result["_report"] = {
        "requests_due": len(due),
        "requests_completed": sum(1 for r in due if r.finish_reason == "length"),
        "requests_failed": len(failed),
        "sessions_started": run.sessions_started,
        "generator_lateness_p95_ms": lateness,
        "compiles_in_window": n_compiled, "traces_in_window": n_traced,
        "setup_s": setup_s, "setup_split_s": split,
        "peak_bytes_in_use": peak_bytes,
        "window_output_tok_s": output_tok_s(ctx),
        "queue_depth_at_open": run.snap["w0"]["queue_depth"],
        "queue_depth_at_close": run.snap["w1"]["queue_depth"],
        "queue_depth_end": run.queue_depth_end,
        "sampled_requests": [(len(r.prompt), len(r.tokens)) for r in picked],
    }
    if control:
        result["_report"]["control_widest_logit_gap"] = control_gap
    return result


def emit(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    report = result.pop("_report")
    for k, v in report.items():
        print(f"{k}: {v}", file=out)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    print(json.dumps(result), file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.resolve(spec.load_benchmark(ROOT), args.workload, ROOT)
    except (FileNotFoundError, KeyError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    from harness.device import CompileLog, check_devices, enable_compile_cache
    try:
        device = check_devices(cell.chips)
    except NoChip as e:
        print(f"perfbench: {e}; the benchmark runs only on the chip",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache(ROOT)}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, CompileLog(), T_START)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
