"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
`BENCHMARK.json` names the cell's configuration and mix,
`bench/configs/<config>.json` names the driver (`bench/drivers/<driver>.py`)
and the plain reference, `bench/traffic/<mix>.json` holds the mix's
parameters, and each metric is read by `bench/metrics/<metric>.py`. A new
cell, configuration, mix or metric is new files and new entries there;
nothing here changes.

The run needs an accelerator: without one, or with fewer chips than the
cell asks for, it exits nonzero and prints no result. It builds weights
and inputs from `--seed`, warms every shape the cell uses (set-up),
measures for `--seconds`, checks the outputs of the timed path against the
plain reference, prints each compared number beside its limit as the last
lines of standard error, and prints one JSON object as the last line of
standard output. `--trace 1` takes a profiler trace of part of the window
and reports the cell's per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


class SetupError(Exception):
    """The cell cannot run here: missing files, no accelerator."""


def load_module(path: Path, name: str):
    """Import the file at `path` as module `name` (file names may hold
    dots and dashes, which `import` refuses)."""
    if not path.is_file():
        raise SetupError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict[str, Any]:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise SetupError(f"no {path}")
    return json.loads(path.read_text())


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SetupError(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of `cell` reports: its end-to-end ones, or with
    a trace its per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, record: Dict) -> Optional[float]:
    """Metric `name` of a run record, by its reader
    `bench/metrics/<name>.py`; None where the reader finds nothing."""
    mod = load_module(BENCH / "metrics" / f"{name}.py", f"metric_{name}")
    return mod.read(record)


class Context:
    """What a driver is given: the cell, its files, the run's arguments,
    and the clock and compile count of the process."""

    def __init__(self, cell: Dict, config: Dict, mix: Dict, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 compiles: Callable[[], int], control: bool = False):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        self.compiles = compiles
        # also read the control (the reference one precision lower) on the
        # same sample: for `control.py`, never in a benchmark run
        self.control = control

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


def compile_counter() -> Callable[[], int]:
    """Count of programs lowered so far in this process (each new jit
    shape lowers once, whether XLA then compiles it or finds it in the
    persistent cache)."""
    from jax import monitoring

    n = [0]

    def on_event(name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            n[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    return lambda: n[0]


def device_info(chips: int) -> Dict[str, Any]:
    """The accelerator as JAX reports it; SetupError without a TPU or with
    fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise SetupError(f"cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def load_cell(name: str):
    """(BENCHMARK.json, the cell's entry, its configuration, its mix)."""
    bench = benchmark()
    cell = find(bench["workloads"], name, "workload")
    path = BENCH / "configs" / f"{cell['config']}.json"
    if not path.is_file():
        raise SetupError(f"no configuration file {path}")
    from traffic import generator

    return bench, cell, json.loads(path.read_text()), \
        generator.load(cell["traffic"])


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, **kw) -> Dict[str, Any]:
    """Run cell `name` of BENCHMARK.json once; return its result object."""
    return run_loaded(*load_cell(name), seed, seconds, trace,
                      t_start=t_start, **kw)


def run_loaded(bench: Dict, cell: Dict, config: Dict, mix: Dict, seed: int,
               seconds: float, trace: bool, *, t_start: float = T_START,
               require_chip: bool = True, control: bool = False
               ) -> Dict[str, Any]:
    """Run a cell given its loaded entries. `require_chip=False` skips the
    look for an accelerator (tests on the CPU); `control=True` also reads
    the control on the checked sample (`control.py`)."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache

    if require_chip:
        compile_cache.enable()
        device = device_info(cell["chips"])
    else:
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    compiles = compile_counter()
    driver = load_module(BENCH / "drivers" / f"{config['driver']}.py",
                         f"driver_{config['driver']}")
    ctx = Context(cell, config, mix, seed, seconds, trace, t_start, compiles,
                  control)
    record = driver.run(ctx)
    record["device"] = device
    ctx.log(f"compiles inside the window: {record['compiles_in_window']}")

    metrics = {}
    for m in metrics_for(bench, cell["name"], trace):
        v = read_metric(m["name"], record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = record["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev_out = dict(device, memory_peak_bytes=record["memory_peak_bytes"])
    if trace:
        dev_out["busy_s"] = record["trace"]["busy_s"]
        dev_out["window_s"] = record["trace"]["window_s"]
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": dev_out,
           "compiles_in_window": record["compiles_in_window"]}
    if trace:
        out["breakdown"] = record["trace"]["breakdown"]
    out["checks"] = checks
    out["record"] = record
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (SetupError, ImportError, FileNotFoundError) as e:
        print(f"cannot run {args.workload}: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr, flush=True)
    out.pop("record")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
