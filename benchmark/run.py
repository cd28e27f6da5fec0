"""Run one benchmark cell once and print its result line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file (``configs/<config>.json``), its traffic mix
(``traffic/<mix>.json``, read by the driver its ``kind`` names), each
metric's reader (``metrics/<metric>.py``) and the device's peaks
(``peaks.json``). A run that finds no TPU, or fewer chips than the cell
asks for, exits 2 and prints no result. With ``--trace 0`` the result
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a span trace of the window and a profiler trace of a
part of it. The last line of standard output is the result; the numbers
compared to decide ``correct`` are the last lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_ROOT = os.path.join(ROOT, ".bench_traces")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_plan(bench, name):
    """(cell, configuration, traffic mix) of the cell called ``name``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT, entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, cfg, mix


def pin_chips(chips):
    """One-chip cells see chip 0 only (libtpu's per-process bounds, as
    chip_smoke.pin_first_chip); must run before JAX starts."""
    if chips == 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR already points), every program
    cached, so only a cell's first run in a checkout compiles."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def applies(metric, cell_name, end_to_end):
    """Whether ``metric`` is reported in the cell: its ``workloads`` list,
    or, without one, every cell (end-to-end) or every cell that reports
    the end-to-end metric it moves (per-layer)."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if metric.get("moves") is None:
        return True
    return applies(end_to_end[metric["moves"]], cell_name, end_to_end)


def read_metric(name, run):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def reduce_device_trace(run, profile_dir):
    """Device busy time, module and op times, collectives and idle gaps of
    the profiled part of the window, into ``run.device``."""
    import trace_reduce as tr
    from drivers import WINDOW_MARK

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths or run.profile_window is None:
        return
    trace = tr.load(max(paths, key=os.path.getmtime))
    planes = tr.device_planes(trace)
    offset = tr.clock_offset(trace, WINDOW_MARK, run.profile_window[0])
    if not planes or offset is None:
        return

    def ns(t):
        return t * 1e9 + offset

    if run.kind == "burst":
        cyc = run.cycles[0]
        windows = [(ns(cyc["t0"]), ns(cyc["t2"]))]
    else:
        windows = [(ns(run.profile_window[0]), ns(run.profile_window[1]))]
    window_ns = sum(hi - lo for lo, hi in windows)
    busy = [tr.busy_ns(trace, p, windows) for p in planes]
    ops = tr.op_time_ns(trace, planes[0], windows)
    spans = [(name, ns(t0), ns(t1)) for name, t0, t1, _ in run.spans]
    run.device = {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window_ns / 1e9,
        "modules_ns": tr.op_time_ns(trace, planes[0], windows,
                                    line=tr.MODULES_LINE),
        "collective_ns": sum(tr.op_time_ns(
            trace, planes[0], windows, match=tr.COLLECTIVE).values()),
        "cycles_profiled": len(windows),
        "top_ops": sorted(([k, v / 1e9] for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[label, gap / 1e9] for label, gap in tr.idle_gaps(
            trace, planes[0], windows[0], spans)],
    }


def measure(bench, cell, cfg, mix, seed, seconds, trace, platform, devices):
    """Run the cell and build its result (everything but the chip check,
    which :func:`main` makes first)."""
    import drivers

    profile_dir = os.path.join(TRACE_ROOT, cell["name"])
    if trace:
        shutil.rmtree(profile_dir, ignore_errors=True)
    run = drivers.DRIVERS[mix["kind"]](
        cfg, mix, seed, seconds, trace, platform, T_START, profile_dir)
    if trace and platform == "tpu":
        reduce_device_trace(run, profile_dir)
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    wanted = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = {}
    for m in wanted:
        if not applies(m, cell["name"], end_to_end):
            continue
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    result = {
        "correct": all(v == 0 for v in run.checks.values()),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and run.device is not None:
        device["busy_s"] = run.device["busy_s"]
        device["window_s"] = run.device["window_s"]
        result["breakdown"] = {
            "device_ops": run.device["top_ops"],
            "idle_gaps": run.device["idle_gaps"],
        }
    # Every number compared, beside its limit: an exact comparison, 0.
    result["checks"] = {k: {"value": v, "limit": 0}
                        for k, v in run.checks.items()}
    return result, run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, cfg, mix = cell_plan(bench, args.workload)
    pin_chips(cell["chips"])
    if args.trace:
        os.environ["KBT_TRACE_JAX"] = "1"  # spans into the XLA profile
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX sees {len(devices)} {platform} device(s)",
              file=sys.stderr)
        return 2
    peaks = load_json(HERE, "peaks.json")["devices"]
    if devices[0].device_kind not in peaks:
        print(f"run.py: no peaks for {devices[0].device_kind!r} in "
              "peaks.json", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    use_compile_cache()
    result, _ = measure(bench, cell, cfg, mix, args.seed, args.seconds,
                        args.trace, platform, devices[:cell["chips"]])
    for name, check in result["checks"].items():
        print(f"check {name}: {check['value']} (limit {check['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
