"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration (``bench/configs/<config>.json``) and the model module it
names (``bench/models/<model>.py``), its traffic mix
(``bench/traffic/<traffic>.json``), whose ``driver`` names
``bench/drivers/<driver>.py``, its correctness limits
(``bench/limits/<workload>.json``) and, with ``--trace 1``, a reader per
per-layer metric (``bench/metrics/<name>.py``, else the file of the
name's part before its first dot).

It needs a TPU: on any other backend, or with fewer chips than the cell
asks for, it exits non-zero and prints no result. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with a trace), then
``check``, the numbers compared with their limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
OUT = ROOT / ".bench_out"


def fail(msg: str, code: int = 1):
    print(f"bench/run.py: {msg}", file=sys.stderr)
    sys.exit(code)


class CompileCount:
    """Programs XLA built or loaded from the persistent cache (`jax.monitoring`),
    in all and inside the window; and how many of them the cache held."""

    def __init__(self):
        import jax

        self.total = self.in_window = self.cache_hits = 0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += 1
            self.in_window += self._open

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def window_open(self):
        self._open = True

    def window_close(self):
        self._open = False


def cell_of(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r} in BENCHMARK.json", 2)
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return w, configs[w["config"]]


def metrics_of(entries: list, workload: str) -> list:
    """The entries that a cell reports: those that list it, or list no cell."""
    return [e for e in entries if workload in e.get("workloads", [workload])]


def reader(name: str):
    for stem in (name, name.split(".")[0]):
        path = ROOT / "bench" / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    fail(f"no reader for per-layer metric {name!r}", 2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        with open(ROOT / "BENCHMARK.json") as fh:
            bench = json.load(fh)
        from bench import check, models, work
        from bench.traffic import load_mix
        from bench.weights import load_config
        from repro.compile_cache import place_compile_cache, place_tpu_logs
    except (ImportError, OSError) as e:
        fail(f"cannot load the benchmark or the program: {e}", 2)
    w, _ = cell_of(bench, args.workload)
    try:
        cfg = load_config(w["config"])
        models.load(cfg)
    except (ImportError, OSError, KeyError) as e:
        fail(f"cannot load the cell's configuration or its model: {e}", 2)

    place_tpu_logs()
    place_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(w["chips"]):
        fail(f"the cell needs {w['chips']} TPU chip(s); JAX found {len(devices)} "
             f"{devices[0].platform} device(s)")
    used = devices[: int(w["chips"])]
    line = measure(bench, args.workload, cfg, load_mix(w["traffic"]),
                   check.load_limits(args.workload), args.seed, args.seconds,
                   bool(args.trace), used, work.peaks(used[0].device_kind))
    print(json.dumps(line), flush=True)
    return 0


def measure(bench: dict, workload: str, cfg: dict, mix: dict, limits: dict, seed: int,
            seconds: float, trace: bool, used: list, peak: dict) -> dict:
    """Drive one run of a cell on ``used`` devices; its result line.

    Everything after the look for chips: the driver's set-up, window and
    check, the trace's reduction and the per-layer readers.
    """
    from bench import models
    from bench.serving import NAMES
    from bench.trace_reduce import reduce_trace

    compiles = CompileCount()
    trace_dir = OUT / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)

    def memory_peak():
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)

    ctx = SimpleNamespace(
        workload=workload, cfg=cfg, mix=mix, seed=seed, seconds=seconds, trace=trace,
        trace_dir=trace_dir, t_start=T_START, compiles=compiles, memory_peak=memory_peak,
        limits=limits, peak=peak)
    res = importlib.import_module(f"bench.drivers.{mix['driver']}").run(ctx)

    e2e = metrics_of(bench["end_to_end"], workload)
    device = {"platform": used[0].platform, "kind": used[0].device_kind, "count": len(used),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["verdict"]["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    print(f"compiles: {compiles.total} in all ({compiles.cache_hits} from the persistent "
          f"cache), {compiles.in_window} inside the window", file=sys.stderr)
    print(f"run: setup_s {res['metrics']['setup_s']} {json.dumps(res['summary'])}",
          file=sys.stderr)
    if not trace:
        line["metrics"] = {e["name"]: {"value": res["metrics"][e["name"]], "unit": e["unit"]}
                           for e in e2e}
        line["device"] = device
    else:
        tw = res["trace"]
        red = reduce_trace(trace_dir)
        window_s = red.get("window_s") or tw.t_off - tw.t_on
        m = SimpleNamespace(trace=red, tw=tw, cfg=cfg, model=models.load(cfg), peak=peak,
                            window_s=window_s, names=NAMES)
        values = {}
        if red.get("devices"):
            for e in metrics_of(bench["per_layer"], workload):
                v = reader(e["name"])(m)
                if v is not None:
                    values[e["name"]] = {"value": v, "unit": e["unit"]}
        line["metrics"] = values
        line["device"] = device | {"busy_s": red.get("busy_s", 0.0), "window_s": window_s}
        if red.get("devices"):
            top = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
            line["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                                 "idle_gaps": red["idle_gaps"][:10]}
            by_span = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])
            print(f"trace: modules {json.dumps(red['module_s'])} counts "
                  f"{json.dumps(red['module_n'])}; idle by host span {json.dumps(by_span)}",
                  file=sys.stderr)
    verdict = res["verdict"]
    print(f"check: {json.dumps({k: v for k, v in verdict.items() if k != 'numbers'})}",
          file=sys.stderr)
    for k, v in verdict["numbers"].items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    line["check"] = verdict["numbers"]
    sys.stderr.flush()
    return line


if __name__ == "__main__":
    sys.exit(main())
