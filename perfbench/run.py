"""One run of one benchmark cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is data: the cell comes from ``BENCHMARK.json``, its
configuration from the ``file`` of its ``configs`` entry, its traffic from
``<paths>/traffic/<traffic>.json``, the runner from
``<paths>/runners/<kind>.py`` by the traffic file's ``kind``, and each
per-layer metric from ``<paths>/layer_metrics/<metric name>.py`` (one
function ``read(record)``; ``None`` leaves the metric out).  A new
configuration, traffic mix, kind of runner or per-layer metric is new
files plus new entries; nothing here is edited.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a run with a short profiler window.  Without a
TPU (or with fewer chips than the cell asks for) the run exits non-zero
and prints no result.  The LAST line of stdout is the result object;
losses, utilisation, histograms and the device line go on earlier lines.
"""
import time
T0 = time.perf_counter()          # set-up is counted from here

import argparse                   # noqa: E402
import collections                # noqa: E402
import importlib.util             # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import sys                        # noqa: E402
import types                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def say(**kw):
    print(json.dumps(kw, default=str), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    name = "perfbench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(benchmark_json, workload):
    """(BENCHMARK.json, the cell, its configuration file, its traffic
    file, ``find(sub, name)`` over the benchmark's directories)."""
    bench = load_json(benchmark_json)
    base = os.path.dirname(os.path.abspath(benchmark_json))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        sys.exit(f"no cell {workload!r} in {benchmark_json}")
    dirs = [os.path.join(base, p) for p in bench["paths"]] + [HERE]

    def find(sub, name):
        for d in dirs:
            path = os.path.join(d, sub, name)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"{sub}/{name} under none of {dirs}")

    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(base, entry["file"]))
    traffic = load_json(find("traffic", cell["traffic"] + ".json"))
    return bench, cell, config, traffic, find


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # For the benchmark's own tests only: another BENCHMARK.json (a tiny
    # configuration, never a cell) and leave to run it on the CPU.
    ap.add_argument("--benchmark-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args(argv)

    bench, cell, config, traffic, find = resolve(args.benchmark_json,
                                                 args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    runner = load_module(find("runners", traffic["kind"] + ".py"))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse_on_cpu:
        sys.exit(f"the benchmark needs a TPU; jax found "
                 f"{devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        sys.exit(f"cell {cell['name']} needs {cell['chips']} chip(s); jax "
                 f"found {len(devices)}")
    if devices[0].platform == "tpu":
        from perfbench.lib.peaks import chip_peaks
        peaks = chip_peaks(devices[0].device_kind)   # unknown kind raises
    else:
        peaks = None

    # Compilations, counted from JAX's own monitoring events (one per
    # backend compile or persistent-cache load): the window must hold 0.
    compile_events = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_events.update(
            {"n": 1, "seconds": secs})
        if name.endswith("backend_compile_duration") else None)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()       # <checkout>/.jax_cache, or
    #                                          $JAX_COMPILATION_CACHE_DIR
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    say(phase="start", cell=cell["name"], config=cell["config"],
        traffic=cell["traffic"], chips=cell["chips"], seed=args.seed,
        seconds=seconds, trace=args.trace, jax=jax.__version__,
        platform=devices[0].platform, device_kind=devices[0].device_kind,
        device_count=len(devices), compile_cache_dir=cache_dir,
        rehearsal=bool(args.rehearse_on_cpu))

    marks = [("imports_and_device", time.perf_counter() - T0)]
    ctx = types.SimpleNamespace(
        mark=lambda name: marks.append((name, time.perf_counter() - T0)),
        marks=marks,
        t0=T0, root=ROOT, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        devices=devices[:cell["chips"]], peaks=peaks, say=say,
        rehearsal=devices[0].platform != "tpu",
        compile_events=compile_events,
        trace_dir=os.path.join(HERE, ".out", "trace", cell["name"]))
    record = runner.run(ctx)

    if args.trace:
        metrics = {}
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            value = load_module(
                find("layer_metrics", m["name"] + ".py")).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(record["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in bench["end_to_end"] if applies(m, cell["name"])}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(record["memory_peak_bytes"])}
    result = {"correct": bool(record["correct"]),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]),
              "metrics": metrics, "device": device}
    if args.trace:
        from perfbench.lib import xplane
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = xplane.breakdown(record["trace"])
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
