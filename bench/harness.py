"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything a cell is made of is found by name, in files of its own:

* ``<root>/configs/<config>.json``: the configuration (input, codec
  settings, source, cuts, limits of the correctness checks);
* ``<root>/inputs/<kind>.py``: the maker of the configuration's input;
* ``<root>/traffic/<traffic>.json``: the operations of set-up and window,
  as data;
* ``<root>/ops/<op>.py``: each operation a traffic file names
  (``bench/session.py`` says what these two kinds of file hold);
* ``<root>/metrics/<metric>.py``: a reader ``read(run) -> float | None``
  for each metric, end-to-end and per-layer alike.

``roots`` is searched in order, so a test can add a cell from a temporary
directory without touching this one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Layout:
    """Where a benchmark's files are: ``BENCHMARK.json`` and the roots."""

    def __init__(self, benchmark_json: str = None, roots=None):
        self.benchmark_json = benchmark_json or os.path.join(
            REPO_DIR, "BENCHMARK.json")
        self.roots = list(roots or [BENCH_DIR])
        self._modules = {}

    def benchmark(self) -> dict:
        with open(self.benchmark_json) as f:
            return json.load(f)

    def find(self, sub: str, filename: str) -> str:
        for root in self.roots:
            path = os.path.join(root, sub, filename)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"no {sub}/{filename} under {self.roots}")

    def load_json(self, sub: str, name: str) -> dict:
        with open(self.find(sub, name + ".json")) as f:
            return json.load(f)

    def plugin(self, sub: str, name: str):
        """The module ``<root>/<sub>/<name>.py``, loaded once."""
        path = self.find(sub, name + ".py")
        if path not in self._modules:
            mod_name = f"bench_{sub}_" + "".join(
                c if c.isalnum() else "_" for c in name)
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def reader(self, metric: str):
        return self.plugin("metrics", metric).read


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    layout: Layout


def load_cell(layout: Layout, workload: str) -> Cell:
    bench = layout.benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in e2e_names]
    return Cell(name=workload, chips=int(w["chips"]),
                config=layout.load_json("configs", w["config"]),
                traffic=layout.load_json("traffic", w["traffic"]),
                end_to_end=e2e, per_layer=per_layer, layout=layout)


@dataclasses.dataclass
class Run:
    """What a run recorded; the metric readers take their numbers from it.

    ``ops``: the window's operations (``ops.Outcome``) in order, with
    ``stats``: the codec counters' change over each.  ``trace``: the
    reduced profiler trace (``--trace 1`` only).
    """

    cell: Cell
    setup_s: float
    ops: list
    stats: list
    ratio: "float | None"
    trace: object = None
    peaks: "dict | None" = None

    def ops_of(self, kind: str) -> list:
        return [o for o in self.ops if o.work.kind == kind]

    def stats_of(self, kind: str) -> list:
        return [s for o, s in zip(self.ops, self.stats)
                if o.work.kind == kind]


def _counters(codec_config) -> dict:
    from repro.core import Codec

    return {k: int(v) for k, v in Codec(codec_config).stats.items()}


def _delta(before: dict, after: dict) -> dict:
    return {k: after.get(k, 0) - before.get(k, 0) for k in after}


def _fingerprint_fn():
    import jax
    import jax.numpy as jnp

    def fp(x):
        u = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)
        i = jnp.arange(u.shape[0], dtype=jnp.uint32)
        return jnp.stack([jnp.sum(u), jnp.sum(u * (2 * i + 1))])

    return jax.jit(lambda d: {k: fp(v) for k, v in d.items()})


def _settle(out):
    """Clean up after an operation, outside its timed span, and flush what
    it wrote to disk.  A deployment's saves lie minutes apart; without the
    flush, the write-back of one save's files lands inside the next."""
    if out.after:
        out.after()
    os.sync()


def device_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device {kind!r} is not in bench/peaks.json")
    return table[kind]




def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True) -> dict:
    """Run ``cell`` once; return its result as a dict (the printed line).

    ``require_tpu=False`` is the CPU rehearsal: the run goes through every
    operation and check, and reports no metric.
    """
    import jax

    from bench.session import Session

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoDevice(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < cell.chips:
            raise NoDevice(f"{cell.name} needs {cell.chips} chips, found "
                           f"{len(devices)}")
        peaks = device_peaks(devices[0].device_kind)
    else:
        peaks = None
    used = devices[:cell.chips]
    log(f"{cell.name}: {len(devices)} x {devices[0].device_kind}, "
        f"seed {seed}, {seconds} s, trace {int(trace)}")

    log(f"set-up: start {time.perf_counter() - t_start:.2f} s")
    workdir = tempfile.mkdtemp(prefix="bench-")
    try:
        s = Session(cell.config, seed, cell.chips, workdir, cell.layout)
        t = time.perf_counter()
        s.make_inputs()
        log(f"set-up: input {time.perf_counter() - t:.2f} s")
        for op in cell.traffic.get("setup", []):
            out = s.run_op(op)
            log(f"set-up: {op['op']} {out.t1 - out.t0:.2f} s")
        window_ops = cell.traffic["window"]
        fingerprint = _fingerprint_fn()
        # Warm-up: every operation of the window once, with its checks.
        for op in window_ops:
            out = s.run_op(op)
            log(f"set-up: warm-up {op['op']} {out.t1 - out.t0:.2f} s")
            if out.answer is not None:
                jax.block_until_ready(fingerprint(out.answer))
            _settle(out)
            del out
        if not any(s.op(op["op"]).READS_INPUT for op in window_ops):
            s.inputs = None          # the window reads files only
        record = _window(s, window_ops, seed, seconds, trace, fingerprint,
                         workdir, t_start)
        return _finish(s, cell, record, peaks, used, devices, trace,
                       require_tpu)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _window(s, window_ops, seed, seconds, trace, fingerprint, workdir,
            t_start) -> dict:
    import jax

    rng = random.Random(seed)
    rec = {"ops": [], "stats": [], "prints": [], "failed": 0, "stale": 0,
           "missing": 0, "sample": None, "n_answers": 0, "trace_dir": None}
    names = set(s.shapes)
    if trace:
        rec["trace_dir"] = os.path.join(workdir, "trace")
        jax.profiler.start_trace(rec["trace_dir"])
    t0 = time.perf_counter()
    rec["setup_s"] = t0 - t_start
    log(f"set-up: total {rec['setup_s']:.2f} s")
    win = jax.profiler.TraceAnnotation("bench.window") if trace else None
    if win:
        win.__enter__()
    i = 0
    while time.perf_counter() - t0 < seconds:
        op = window_ops[i % len(window_ops)]
        i += 1
        before = _counters(s.codec_config)
        try:
            if trace:
                with jax.profiler.TraceAnnotation(
                        "bench." + s.op(op["op"]).KIND):
                    out = s.run_op(op)
            else:
                out = s.run_op(op)
        except Exception:
            rec["failed"] += 1
            log(f"operation {op['op']} failed:\n{traceback.format_exc()}")
            continue
        log(f"window: {op['op']} {out.t1 - out.t0:.3f} s")
        rec["stats"].append(_delta(before, _counters(s.codec_config)))
        rec["ops"].append(out)
        if out.answer is not None:
            rec["n_answers"] += 1
            ans = out.answer
            if set(ans) != names:
                rec["missing"] += 1
            if out.step is not None and out.step != s.saved_step:
                rec["stale"] += 1
            rec["prints"].append(fingerprint(
                {k: v for k, v in ans.items() if k in names}))
            if rng.random() * rec["n_answers"] < 1.0:
                rec["sample"] = (len(rec["prints"]) - 1, ans)
        out.answer = None
        _settle(out)
    if win:
        win.__exit__(None, None, None)
    if trace:
        jax.profiler.stop_trace()
    return rec


def judge(s, rec: dict, limits: dict) -> "tuple[bool, dict]":
    """``(correct, checks)`` of a run, once the window has closed.

    The answer drawn from the seed is compared in full with the reference
    (``bench/reference.py``), on the host; every other answer has to carry
    the same fingerprint.  ``rec`` is what the window recorded: ``sample``
    as ``(index, answer)``, ``prints`` (the answers' fingerprints),
    ``missing``, ``stale``, ``failed`` and ``n_answers``.  A control puts
    its own answer in ``sample`` and goes through the same judgement.
    """
    import jax
    import numpy as np

    from bench import reference

    prints = [{k: np.asarray(v) for k, v in p.items()}
              for p in jax.device_get(rec["prints"])]
    sample_at, sample = rec["sample"] if rec["sample"] else (None, None)
    differing = 0
    if sample is not None:
        ref = prints[sample_at]
        differing = sum(
            any(not np.array_equal(p.get(k), ref.get(k)) for k in ref)
            for p in prints)
        sample = {k: np.asarray(v) for k, v in sample.items()}
    rec["prints"] = prints
    rec["sample"] = None

    # The reference runs once the program's arrays are gone, on the host.
    if s.inputs is None:
        s.make_inputs()
    inputs = {k: np.asarray(v) for k, v in s.inputs.items()}
    s.inputs = None
    t_ref = time.perf_counter()
    tally = reference.compare(inputs, sample or {}, s.spec, s.eb)
    log(f"reference comparison {time.perf_counter() - t_ref:.1f} s")
    numbers = reference.checks(
        tally, limits, missing=rec["missing"] + (sample is None),
        stale=rec["stale"], differing=differing)
    correct = (rec["failed"] == 0 and rec["n_answers"] > 0
               and reference.passes(numbers))
    return correct, numbers


def _finish(s, cell, rec, peaks, used, devices, trace, require_tpu) -> dict:
    from bench import trace as T

    memory_peak = None
    stats = [d.memory_stats() for d in used]
    if all(stats):
        memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in stats)

    correct, checks = judge(s, rec, cell.config["checks"])

    ratio = None
    if s.disk_bytes:
        ratio = s.total_bytes() / s.disk_bytes
    run = Run(cell=cell, setup_s=rec["setup_s"], ops=rec["ops"],
              stats=rec["stats"], ratio=ratio, peaks=peaks)
    result = {"correct": bool(correct), "attempted": len(rec["ops"])
              + rec["failed"], "failed": rec["failed"], "metrics": {}}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    if trace:
        run.trace = T.load(rec["trace_dir"])
        windows = run.trace.spans_of("window")
        if windows:
            busy = [T.busy_ns(run.trace, d, windows)
                    for d in run.trace.devices]
            device["busy_s"] = (sum(busy) / len(busy) / 1e9) if busy else 0.0
            device["window_s"] = T.length(windows) / 1e9
            result["breakdown"] = {
                "device_ops": T.top_programs(run.trace, windows),
                "idle_gaps": T.idle_gaps(run.trace, windows[0])}
    if require_tpu:
        wanted = cell.per_layer if trace else cell.end_to_end
        for m in wanted:
            value = cell.layout.reader(m["name"])(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
    result["device"] = device
    result["checks"] = {k: {"value": float(v), "limit": float(lim)}
                        for k, (v, lim) in checks.items()}
    return result


def main(args, t_start: float) -> int:
    cell = load_cell(Layout(), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except NoDevice as e:
        log(str(e))
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
