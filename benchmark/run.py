#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in this process, on the TPU.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<mix>.json`), the mix names its generator (`generators/<name>.py`),
and every per-layer metric has a reader (`layer_metrics/<metric>.py`): all are
found by name, and this file holds none of those names. The run refuses to
start off a TPU, builds the deployment (RSM behind `SidecarHttpGateway`),
warms the cell's own shapes, measures for `--seconds`, frees the deployment,
holds what the timed operations produced to the plain reference
(`reference.py`), and prints one JSON object as its last line. README.md has
the layout and how a later PR adds to it.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

#: The traced run keeps every span of the window: the ring must not evict.
TRACED_MAX_SPANS = 400_000


def load(path: pathlib.Path, kind: str):
    """The module in `path`; its own directory is importable while it loads,
    so that twins share a helper module beside them."""
    if not path.is_file():
        raise harness.refuse(f"unknown {kind}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + kind.replace(" ", "_") + "_" + path.stem.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, str(path.parent))
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(str(path.parent))
    return module


def load_json(path: pathlib.Path, kind: str) -> dict:
    if not path.is_file():
        raise harness.refuse(f"unknown {kind}: no file {path}")
    return json.loads(path.read_text())


def named(entries: list, name: str, kind: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise harness.refuse(
        f"unknown {kind} {name!r}; BENCHMARK.json has "
        f"{', '.join(e['name'] for e in entries)}"
    )


def of_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


class Bench:
    """What a generator gets: the cell's data, the run's arguments, the
    yardstick's modules, and the hooks into the program under test."""

    harness = harness
    reference = reference

    def __init__(self, here: pathlib.Path, cell: dict, config: dict, traffic: dict,
                 end_to_end: list, args, device: dict, peaks: dict, log,
                 tmp: pathlib.Path) -> None:
        self.here, self.cell, self.config, self.traffic = here, cell, config, traffic
        self.end_to_end = [m["name"] for m in end_to_end]
        self._counters = [
            load(path, "counter") for path in sorted((here / "counters").glob("*.py"))
        ]
        self.seed, self.seconds, self.trace = args.seed, args.seconds, bool(args.trace)
        self.device, self.peaks, self.log, self.tmp = device, peaks, log, tmp
        self.sizes = config["sizes"]
        self.key, public, private = reference.new_key_pair(tmp, harness.KEY_ID)
        self._store = harness.store_and_keys(tmp, public, private)
        self.store_root = pathlib.Path(self._store["storage.root"])
        self.deployment = None
        self.observation: dict = {"peaks": peaks, "device": device}
        self._trace_dir = tmp / "profile"

    # -------------------------------------------------- the program under test
    def deploy(self):
        """The configuration's RSM behind its gateway; the traced run turns
        the program's spans on."""
        configs = {**self.config["rsm"], **self._store}
        if self.trace:
            configs.update({
                "tracing.enabled": True, "tracing.max.spans": TRACED_MAX_SPANS,
            })
        self.deployment = harness.Deployment(configs)
        return self.deployment

    def release(self) -> None:
        if self.deployment is not None:
            self.deployment.close()
            self.deployment = None

    def counters(self) -> dict:
        """The program's exact counts, as they stand: every file under
        `counters/` reads some, and a later PR adds a file for a new one."""
        counts: dict = {}
        for module in self._counters:
            counts.update(module.read(self.deployment))
        return counts

    # --------------------------------------------------------- the measurement
    def open_window(self) -> None:
        """Called by the generator as its last act before the first timed
        request: set-up ends here."""
        self.setup_s = time.perf_counter() - _T0
        self._compiled_in_setup = self.log.mark()
        self._at_open = self.counters()
        if self.trace:
            self.deployment.rsm.tracer.clear()

    def close_window(self, **window) -> None:
        """Called once the last timed reply is read; `window` is what the
        per-layer readers divide by (seconds, operations, bytes)."""
        at_close = self.counters()
        self.observation["window"] = window
        self.observation["counters"] = {
            k: at_close[k] - self._at_open[k] for k in at_close
        }
        self.memory_peak_bytes = harness.device_peak_bytes()
        compiled = self.log.mark()
        self.compiled_in_window = compiled["programs"] - self._compiled_in_setup["programs"]
        harness.emit({
            "phase": "window", **window, "setup_s": round(self.setup_s, 3),
            "compiled_in_setup": self._compiled_in_setup,
            "programs_compiled_in_window": self.compiled_in_window,
            "counters": self.observation["counters"],
        })
        if self.trace:
            tracer = self.deployment.rsm.tracer
            if tracer.dropped_spans:
                raise harness.refuse(
                    f"the span ring dropped {tracer.dropped_spans} spans: a total "
                    "over it would be short"
                )
            self.observation["spans"] = tracer.summary()
            for name, row in sorted(self.observation["spans"].items()):
                harness.emit({"span": name, **{k: round(v, 6) for k, v in row.items()}})

    @contextlib.contextmanager
    def stretch(self):
        """A steady stretch of the traced run's window under the profiler;
        nothing in an untraced run."""
        if not self.trace:
            yield
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self._trace_dir), profiler_options=options)
        before, start = self.counters(), time.perf_counter()
        try:
            yield
        finally:
            seconds = time.perf_counter() - start
            after = self.counters()
            jax.profiler.stop_trace()
            self.observation["stretch"] = {
                "seconds": seconds,
                "counters": {k: after[k] - before[k] for k in after},
            }

    def read_trace(self) -> None:
        stretch = self.observation.get("stretch")
        if stretch is None:
            raise harness.refuse("the traced run's generator opened no stretch")
        stretch.update(trace_reduce.reduce(self._trace_dir, stretch["seconds"]))
        harness.emit({"phase": "traced_stretch", **stretch})
        if not stretch["busy_s"] > 0:
            raise harness.refuse("no operation ran on the device in the traced stretch")


def peaks_for(here: pathlib.Path, kind: str) -> dict:
    table = load_json(here / "peaks.json", "table of peaks")["devices"]
    if kind not in table:
        raise harness.refuse(
            f"device kind {kind!r} is not in peaks.json ({', '.join(table)}): "
            "a roofline needs a published peak"
        )
    return table[kind]


def require(requires: dict) -> None:
    """What a configuration requires of the build it runs on."""
    if "zstd_engine" in requires:
        from tieredstorage_tpu.transform.tpu import TpuTransformBackend

        engine = TpuTransformBackend.zstd_engine()
        harness.emit({"phase": "requires", "zstd_engine": engine})
        if engine != requires["zstd_engine"]:
            raise harness.refuse(
                f"the host codec runs on {engine!r}, the configuration requires "
                f"{requires['zstd_engine']!r} (native/ did not build or load)"
            )


def main(argv: list[str] | None = None, here: pathlib.Path = HERE) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", default=None,
                        help="break the program as controls/<name>.py says "
                             "(the output check's controls; never the driver's)")
    args = parser.parse_args(argv)

    root = here.parent
    bench_json = load_json(root / "BENCHMARK.json", "benchmark")
    cell = named(bench_json["workloads"], args.workload, "workload")
    config_entry = named(bench_json["configs"], cell["config"], "configuration")
    config = load_json(root / config_entry["file"], "configuration")
    traffic = load_json(here / "traffic" / f"{cell['traffic']}.json", "traffic mix")
    generator = load(here / "generators" / f"{traffic['generator']}.py", "generator")
    end_to_end = of_cell(bench_json["end_to_end"], cell["name"])
    per_layer = of_cell(bench_json["per_layer"], cell["name"])
    readers = {
        m["name"]: load(here / "layer_metrics" / f"{m['name']}.py", "per-layer metric")
        for m in per_layer
    }
    control = (
        load(here / "controls" / f"{args.control}.py", "control")
        if args.control else None
    )

    harness.refuse_kernel_switches()
    sys.path.insert(0, str(root))  # the program of this checkout
    device = harness.require_tpu(cell["chips"])
    peaks = peaks_for(here, device["kind"])
    require(config.get("requires", {}))

    from tieredstorage_tpu.utils.platforms import enable_compile_cache

    cache_dir = enable_compile_cache()
    harness.emit({
        "phase": "start", "cell": cell["name"], "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace, "control": args.control,
        "device": device, "config": cell["config"], "sizes": config["sizes"],
        "traffic": traffic, "compile_cache_dir": cache_dir,
        "compile_cache_entries": harness.cache_entries(cache_dir),
    })
    if control is not None:
        control.apply()

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="benchmark-"))
    try:
        with harness.CompileLog() as log:
            bench = Bench(here, cell, config, traffic, end_to_end, args, device, peaks,
                          log, tmp)
            run = generator.Traffic(bench)
            try:
                run.set_up()
                measured = run.window()
            finally:
                bench.release()
            if args.trace:
                bench.read_trace()
            compared = run.check()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    if args.trace:
        metrics = {}
        for m in per_layer:
            value = readers[m["name"]].read(bench.observation)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": bench.setup_s, **measured["metrics"]}
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in end_to_end
        }
    compared["failed"] = {"value": measured["failed"], "limit": 0}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in compared.values()),
        "attempted": measured["attempted"],
        "failed": measured["failed"],
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": bench.memory_peak_bytes},
    }
    if args.trace:
        stretch = bench.observation["stretch"]
        result["device"].update(busy_s=stretch["busy_s"], window_s=stretch["window_s"])
        result["breakdown"] = {"device_ops": stretch["device_ops"], "idle_gaps": []}
    result["compared"] = compared
    harness.emit({
        "phase": "end", "compile_cache_entries": harness.cache_entries(cache_dir),
        "compiled": log.mark(),
        "compile_s_each": [[f, round(s, 2)] for f, s in log.compiles if s >= 0.5],
    })
    for name, c in compared.items():
        print(f"compared {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
