"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` (at the root of the checkout) names the cell's
configuration and traffic; everything that belongs to one of them sits in
a file of its own, found by name:

* ``bench/configs/<config>.json`` -- the dataset (generator, sizes, what
  was reduced and why);
* ``bench/generators/<generator>.py`` -- makes the dataset from the seed;
* ``bench/traffic/<traffic>.json`` -- the join: which entry point, count or
  pairs, eps, which check, and the limits of the numbers it compares;
* ``bench/entries/<entry>.py`` -- how one whole join calls the system;
* ``bench/checks/<check>.py`` -- the comparison of the window's answers
  with the plain reference;
* ``bench/metrics/<metric>.py`` -- one reader per metric.

A run: turn the compile cache on, check the chip, make the data from the
seed, warm up with one whole join (set-up ends there), then join back to
back until ``--seconds`` have passed; the window closes at the end of the
first join that ends after that.  With ``--trace 1`` the window runs under
the profiler and with the program's spans on, and the per-layer metrics
are read from them; otherwise the end-to-end metrics.  Once the window has
closed and the peak memory is read, the traffic's check compares every
answer with the plain reference.  The last line of standard
output is the result; the last lines of standard error are the numbers
compared, each beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, the run exits with 2 and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import data as data_mod  # noqa: E402
from bench import trace_reduce, work  # noqa: E402

# the JAX monitoring events a compile inside the window shows up as
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: List[dict]     # end-to-end and per-layer entries that apply


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` declares it."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    metrics = [
        dict(m, kind=kind)
        for kind in ("end_to_end", "per_layer")
        for m in spec[kind]
        if name in m.get("workloads", [name])
    ]
    return make_cell(name, root / cfg_file, w["traffic"], int(w["chips"]), metrics)


def make_cell(name: str, config_file: Path, traffic: str, chips: int,
              metrics: List[dict]) -> Cell:
    config = json.loads(Path(config_file).read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return Cell(name, chips, config, mix, metrics)


class Context:
    """What a metric reader may read; ``value(name)`` runs another reader."""

    def __init__(self, **fields):
        self.__dict__.update(fields)
        self._values: Dict[str, Any] = {}

    def value(self, name: str):
        if name not in self._values:
            mod = load_module(BENCH / "metrics" / f"{name}.py")
            self._values[name] = mod.read(self)
        return self._values[name]

    def stats(self) -> list:
        return [r.stats for _, r in self.joins if r is not None]


def _compile_counter():
    """Counts executables built and persistent-cache loads while ``on``;
    an executable built without a cache load was compiled."""
    import jax

    counts = {"on": False, "compiles": 0, "cache_loads": 0}

    def on_duration(event, duration, **kw):
        if counts["on"] and event == _COMPILE_EVENT:
            counts["compiles"] += 1

    def on_event(event, **kw):
        if counts["on"] and event == _CACHE_HIT_EVENT:
            counts["cache_loads"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return counts


def _window(join: Callable, seconds: float):
    """Whole joins back to back; closes after the first join that ends
    ``seconds`` or more after the window opened."""
    import jax

    joins = []
    with jax.profiler.TraceAnnotation("bench.window"):
        w0 = time.perf_counter()
        while True:
            j0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.join"):
                try:
                    res = join()
                except Exception:  # a failed join is counted, not fatal
                    traceback.print_exc()
                    res = None
            j1 = time.perf_counter()
            joins.append((j1 - j0, res))
            if j1 - w0 >= seconds:
                break
    return w0, time.perf_counter() - w0, joins


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, t0: float = _T0) -> dict:
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
            f"{len(devices)} {devices[0].platform} device(s)"
        )
    used = devices[: cell.chips]
    counter = _compile_counter()
    counter["on"] = True
    x = data_mod.generate(cell.config, seed & (2 ** 64 - 1))
    entry = load_module(BENCH / "entries" / f"{cell.traffic['entry']}.py")
    join = entry.make_join(x, cell.config, cell.traffic, used)
    with jax.default_device(used[0]):
        join()  # warm-up: compiles (or loads) every program the window runs
        counter["on"] = False
        in_setup = dict(counter)
        counter.update(compiles=0, cache_loads=0)
        trace_dir = None
        if trace:
            from repro import obs

            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            # the program's spans become annotations on the profiler's
            # timeline, where trace_reduce reads them
            obs.enable(jax_bridge=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        counter["on"] = True
        w0, window_s, joins = _window(join, seconds)
        counter["on"] = False
        if trace:
            s0 = time.perf_counter()
            jax.profiler.stop_trace()
            trace_s = {"stop_s": time.perf_counter() - s0}
            obs.disable()
            obs.clear()
    setup_s = w0 - t0
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    memory_peak = max((p for p in peaks if p is not None), default=None)
    del join, entry
    gc.collect()

    c0 = time.perf_counter()
    checker = load_module(BENCH / "checks" / f"{cell.traffic['check']}.py")
    ref, worst, failed = checker.check(cell.traffic, x, [r for _, r in joins])
    check_s = time.perf_counter() - c0
    summary = None
    if trace:
        pb = next(Path(trace_dir).rglob("*.xplane.pb"))
        r0 = time.perf_counter()
        summary = trace_reduce.reduce_trace(str(pb))
        trace_s.update(read_s=time.perf_counter() - r0, bytes=pb.stat().st_size)
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = getattr(used[0], "device_kind", used[0].platform)
    ctx = Context(
        cell=cell, joins=joins, window_s=window_s, setup_s=setup_s,
        trace=summary, memory_peak_bytes=memory_peak, chips=cell.chips, ref=ref, x=x,
        peak=lambda: _peak(kind), work=work,
    )
    metrics = {}
    want = "per_layer" if trace else "end_to_end"
    for m in cell.metrics:
        if m["kind"] == want:
            v = ctx.value(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {
        "platform": used[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    out = {
        "correct": failed == 0 and len(joins) > 0,
        "attempted": len(joins),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        devs = [summary.devices[i] for i in sorted(summary.devices)[: cell.chips]]
        device["busy_s"] = sum(d.busy_s for d in devs) / max(len(devs), 1)
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": trace_reduce.top(_mean([d.op_s for d in devs])),
            "idle_gaps": trace_reduce.top(_mean([d.idle_by_span for d in devs])),
        }
    out["setup"] = {
        "compiles": in_setup["compiles"] - in_setup["cache_loads"],
        "cache_loads": in_setup["cache_loads"],
    }
    out["window"] = {
        "joins": len(joins),
        "join_s": [t for t, _ in joins],
        "compiles": counter["compiles"] - counter["cache_loads"],
        "cache_loads": counter["cache_loads"],
        "check_s": check_s,
    }
    if trace:
        out["window"]["trace"] = trace_s
    out["checks"] = {
        k: {"value": worst[k], "limit": cell.traffic["limits"][k]} for k in worst
    }
    return out


def _peak(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def _mean(dicts: List[dict]) -> dict:
    out: Dict[str, float] = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + v / len(dicts)
    return out


def report(out: dict) -> None:
    """Result on stdout's last line; the numbers compared on stderr's."""
    s, w = out["setup"], out["window"]
    print(
        f"setup: {s['compiles']} compiles, {s['cache_loads']} compile-cache loads",
        file=sys.stderr,
    )
    print(
        f"window: {w['joins']} joins, {w['compiles']} compiles, "
        f"{w['cache_loads']} compile-cache loads; checked in {w['check_s']:.1f} s",
        file=sys.stderr,
    )
    if "trace" in w:
        t = w["trace"]
        print(
            f"trace: stopped in {t['stop_s']:.1f} s, {t['bytes']} bytes read in "
            f"{t['read_s']:.1f} s", file=sys.stderr,
        )
    print(json.dumps(out), flush=True)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    report(out)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    sys.exit(main())
