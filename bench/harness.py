"""Run one cell of the benchmark once and print its result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything else
is found by name:

- ``bench/configs/<config>.json``: the configuration as it is run; its
  ``system`` and ``reference`` keys name modules of ``bench/systems``;
- ``bench/traffic/<traffic>.json``: the mix, read by ``bench/generator.py``
  (arrivals, sizes, tenants; its ``inputs.dist`` names a module of
  ``bench/inputs``); its ``request`` names a module of ``bench/requests``
  that sends one request to the system (or to the reference put in its
  place) and receives its answer and counters;
- ``bench/checks/<cell>.json``: how many answers the check samples, which
  statistic of their gaps to the reference it compares, and its limit;
- ``bench/metrics/<metric>.py`` (or ``<base>.py`` for ``<base>.<variant>``):
  a reader ``read(run) -> {name: value}`` that returns nothing where it finds
  nothing to read.

One run: refuse without the chips the cell asks for; turn on the compile
cache; build the configuration on the device from the seed; warm up each
of the mix's request shapes; serve the mix for ``--seconds`` (with
``--trace 1``, under the profiler and for at most ``TRACE_SECONDS``); read
the peak device memory; free the program's state; compare a sample of the
window's answers, drawn from the seed, with the configuration's plain
reference; print the comparison last on standard error and the result as
the last line of standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
# A traced run serves this long at most: a trace of the 65,536^2 cell holds
# over 10^5 device ops a second.
TRACE_SECONDS = 6.0
# What counts as compiling inside the window: tracing, compiling, or
# loading a compiled program from the persistent cache.
COMPILE_EVENTS = ("jaxpr_trace_duration", "backend_compile_duration",
                  "cache_retrieval_time_sec")



class Refused(Exception):
    """The run cannot be made here; no result is printed."""

    def __init__(self, message: str, code: int):
        super().__init__(message)
        self.code = code


# ------------------------------------------------------------------ loading
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"bench: no {what} named {name!r} in BENCHMARK.json", 2)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    check: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cell = _entry(spec["workloads"], name, "workload")
    conf = _entry(spec["configs"], cell["config"], "config")
    mine = lambda m: name in m.get("workloads", [name])
    return Cell(name=name, chips=int(cell["chips"]),
                config=load_json(root / conf["file"]),
                mix=load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
                check=load_json(BENCH / "checks" / f"{name}.json"),
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


# --------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers read it."""

    cell: Cell
    family: str                  # the request kind's FAMILY: "solve", "mvm"
    chips: int
    setup_s: float
    window_s: float              # host clock, first send to last answer
    records: List[dict]          # per answered request: i, cols, due_s,
                                 # sent_s, latency_s (window clock) and the
                                 # request kind's counters
    attempted: int
    failed: int
    peaks: dict
    work: Callable[[int], dict]  # cols -> {"flop", "bytes"} of one MVM
    trace: Optional[object] = None   # a trace.Trace of the window
    reduce: Optional[object] = None  # the trace.py module that reduces it


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Sample:
    """A sample of ``k`` answers drawn from the seed as they arrive
    (reservoir sampling: which ones depends only on the seed and count)."""

    def __init__(self, k: int, seed: int):
        import numpy as np
        self.k = k
        self.rng = np.random.default_rng([seed, 2])
        self.seen = 0
        self.slots: Dict[int, tuple] = {}

    def offer(self, i: int, out) -> None:
        slot = self.seen if self.seen < self.k else \
            int(self.rng.integers(0, self.seen + 1))
        if slot < self.k:
            self.slots[slot] = (i, out)
        self.seen += 1

    @property
    def items(self) -> List[tuple]:
        return sorted(self.slots.values(), key=lambda t: t[0])


def serve_window(system, kind, traffic, seconds: float, sample: Sample,
                 first):
    """Serve the mix's requests that are due within ``seconds``, one after
    another in the order they are due; returns the answered requests'
    records, the attempted and failed counts and the window's length
    (start to last answer).  Request ``i + 1`` is drawn while request ``i``
    runs; request 0 (``first``) was drawn before the window.  The request
    kind's counters stay as the kind returned them (on the device, say)."""
    records, attempted, failed, done = [], 0, 0, []
    req = first
    gc.disable()                # no collector pauses inside the window
    try:
        start = time.perf_counter()
        while (due := traffic.due(req.i, done)) < seconds:
            wait = start + due - time.perf_counter()
            if wait > 0:
                with _span("bench.idle"):
                    time.sleep(wait)
            attempted += 1
            sent_s = time.perf_counter() - start
            try:
                try:
                    with _span("bench.send"):
                        sent = kind.send(system, req, traffic.params)
                finally:
                    dispatched_s = time.perf_counter() - start
                    with _span("bench.input"):
                        nxt = traffic.request(req.i + 1)
                    drawn_s = time.perf_counter() - start
                with _span("bench.wait"):
                    out, counters = kind.receive(sent)
            except Exception as e:   # an answer that never comes
                done.append(time.perf_counter() - start)
                print(f"bench: request {req.i} failed: {e!r}",
                      file=sys.stderr)
                failed += 1
            else:
                done.append(time.perf_counter() - start)
                sample.offer(req.i, out)
                records.append({"i": req.i, "cols": req.cols, "due_s": due,
                                "sent_s": sent_s, "dispatched_s": dispatched_s,
                                "drawn_s": drawn_s, "done_s": done[-1],
                                "latency_s": done[-1] - due, **counters})
            req = nxt
        return records, attempted, failed, time.perf_counter() - start
    finally:
        gc.enable()


def latency_summary(records: List[dict]) -> str:
    """One line on the window's latencies, with the slowest request's time
    in each phase (queued, send, next draw, wait)."""
    if not records:
        return "bench: no request answered"
    lat = sorted(records, key=lambda r: r["latency_s"])
    r = lat[-1]
    phases = {"queued": r["sent_s"] - r["due_s"],
              "send": r["dispatched_s"] - r["sent_s"],
              "draw": r["drawn_s"] - r["dispatched_s"],
              "wait": r["done_s"] - r["drawn_s"]}
    ms = lambda v: f"{1e3 * v:.3f}"
    return (f"bench: {len(lat)} answered; latency ms min "
            f"{ms(lat[0]['latency_s'])} median "
            f"{ms(lat[len(lat) // 2]['latency_s'])} max {ms(r['latency_s'])} "
            f"(request {r['i']} at {r['sent_s']:.3f} s: "
            + ", ".join(f"{k} {ms(v)}" for k, v in phases.items()) + ")")


def gaps(got, want) -> Dict[str, float]:
    """Statistics of an answer's gap to the reference's answer, entry by
    entry, relative to the largest entry of each column of the reference,
    each the largest over the columns: the widest gap, the median and some
    quantiles, and the l2 gap of the column."""
    import jax.numpy as jnp
    got = got.reshape(want.shape)
    diff = jnp.abs(got - want) / jnp.max(jnp.abs(want), axis=0)
    stats = {"widest": jnp.max(diff, axis=0),
             "median": jnp.median(diff, axis=0),
             "p90": jnp.quantile(diff, 0.9, axis=0),
             "p99": jnp.quantile(diff, 0.99, axis=0),
             "l2": jnp.linalg.norm(got - want, axis=0)
             / jnp.linalg.norm(want, axis=0)}
    out = {k: float(jnp.max(v)) for k, v in stats.items()}
    return {k: v if math.isfinite(v) else math.inf for k, v in out.items()}


def compare(kind, traffic, reference, sample: Sample) -> Dict[str, dict]:
    """For each sampled request, the gaps of the window's answer to the
    reference's answer for the same request."""
    found = {}
    for i, out in sample.items:
        want, _ = kind.receive(kind.send(reference, traffic.request(i),
                                         traffic.params))
        found[i] = gaps(out, want)
    return found


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path)


def read_metrics(run: Run, declared: List[dict]) -> Dict[str, dict]:
    out, readers = {}, {}
    for m in declared:
        reader = readers.get(m["name"].split(".")[0])
        if reader is None:
            reader = readers[m["name"].split(".")[0]] = metric_reader(m["name"])
        value = reader.read(run).get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def process_age_s() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"bench: no TPU: JAX sees {len(devs)} "
                      f"{devs[0].platform} device(s) "
                      f"({devs[0].device_kind})", 3)
    if len(devs) < chips:
        raise Refused(f"bench: the cell needs {chips} chips; JAX sees "
                      f"{len(devs)}", 3)
    return devs


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, age0: float = 0.0, require_tpu: bool = True,
             control: Optional[str] = None, cell: Optional[Cell] = None,
             wrap: Optional[Callable] = None) -> dict:
    """One run of ``workload``; returns the result object.

    ``cell`` replaces the cell read from ``BENCHMARK.json`` (tests run one
    at a small size), ``wrap(system)`` replaces the system under test (tests
    plant faults), ``control`` puts the reference at that precision in the
    program's place."""
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"bench: the program is missing: no {ROOT}/src/repro",
                      2)
    for path in (ROOT / "src", BENCH, BENCH / "systems"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    cell = cell or load_cell(workload)
    import jax
    devs = devices_for(cell.chips, require_tpu)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import generator
    import peaks as peak_table
    trace_mod = load_module(BENCH / "trace.py")

    conf = cell.config
    system_mod = importlib.import_module(conf["system"])
    reference_mod = importlib.import_module(conf["reference"])
    traffic = generator.Traffic(cell.mix, conf["n"], seed)
    kind = load_module(BENCH / "requests" / f"{traffic.kind}.py")
    if control is None:
        system = system_mod.build(conf, traffic.program_key, devs)
    else:
        system = reference_mod.Reference(conf, traffic.program_key, control)
    if wrap is not None:
        system = wrap(system)
    with _span("bench.warmup"):
        for _, cols in traffic.shapes:
            kind.receive(kind.send(system, traffic.request(
                0, warmup=True, cols=cols), traffic.params))
    used = devs[:cell.chips]
    first = traffic.request(0)
    jax.block_until_ready(first.x)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_k: compiles.append(event)
        if event.endswith(COMPILE_EVENTS) else None)
    setup_s = age0 + time.perf_counter() - t0
    sample = Sample(int(cell.check["sample"]), seed)
    gc.collect()
    compiled_before = len(compiles)
    tr = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        with _span(trace_mod.WINDOW_SPAN):
            records, attempted, failed, window_s = serve_window(
                system, kind, traffic, min(seconds, TRACE_SECONDS), sample,
                first)
        jax.profiler.stop_trace()
    else:
        records, attempted, failed, window_s = serve_window(
            system, kind, traffic, seconds, sample, first)
    window_compiles = len(compiles) - compiled_before
    records = [{k: v.item() if hasattr(v, "item") else v for k, v in r.items()}
               for r in jax.device_get(records)]   # counters read back now
    print(latency_summary(records), file=sys.stderr)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    system.free()
    del system
    gc.collect()
    if trace:
        tr = trace_mod.read_xplane(trace_mod.find_xplane(str(TRACE_DIR)),
                                   chips=cell.chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    reference = reference_mod.Reference(conf, traffic.program_key, "highest")
    found = compare(kind, traffic, reference, sample)
    for i, g in found.items():
        print(f"bench: request {i} gaps {json.dumps(g)}", file=sys.stderr)
    stat = cell.check["compare"]
    name = f"{kind.ANSWER}_gap_{stat}"
    worst = max((g[stat] for g in found.values()), default=math.inf)
    checked = {name: {"value": worst, "limit": cell.check["limit"]}}
    correct = (failed == 0 and bool(records) and bool(sample.items)
               and all(c["value"] <= c["limit"] for c in checked.values()))

    dev = devs[0]
    run = Run(cell=cell, family=kind.FAMILY, chips=cell.chips, setup_s=setup_s, window_s=window_s,
              records=records, attempted=attempted, failed=failed,
              peaks=peak_table.peaks(dev.device_kind)
              if dev.platform == "tpu" else {},
              work=lambda cols: system_mod.work(conf, cols), trace=tr,
              reduce=trace_mod)
    declared = cell.per_layer if trace else cell.end_to_end
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": read_metrics(run, declared), "device": device}
    if tr is not None:
        device["busy_s"] = trace_mod.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
    result["window_compiles"] = window_compiles
    result["check"] = checked
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell once; the last line of standard "
                    "output is the result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("high",),
                    help="put the plain reference at this precision in the "
                         "program's place (the control of the check; never "
                         "part of a benchmark run)")
    return ap.parse_args(argv)


def main(argv=None, *, t0: Optional[float] = None, age0: float = 0.0) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=t0, age0=age0,
                          control=args.control)
    except Refused as e:
        print(str(e), file=sys.stderr)
        return e.code
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
