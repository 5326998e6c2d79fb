"""One run of one cell: set-up, a window of whole sort jobs, the check.

Everything about a cell is found by name from files of their own:
``BENCHMARK.json`` names the cell's configuration and traffic mix,
``configs/<config>.json`` holds the deployment, ``traffic/<mix>.json`` the
job (the program's command line, the reference, the limits),
``corpus/<generator>.py`` makes and writes the input,
``references/<reference>.py`` says what a job must write,
``metrics/<metric>.py`` reads one per-layer metric, and ``peaks.json``
holds the device's peaks.

The window runs the program's own command line (``cli.main``) back to back
on one input made in set-up.  A job is started while the window is open and
always runs to its end: the rate is the records of every job that did not
fail over the time from the window's start to the end of the last job.
After the window every job's output is compared with the plain reference.

A run gives no result where the program did not take the cell's path:
where the warm-up job, the traced job or every job of the window raised,
exited nonzero or broke a path rule (:func:`failure_reasons`), :func:`run`
raises :class:`OffPath`.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import importlib.util
import io
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: Counters (and prefixes, ending in ".") that record a fallback, a
#: tier-down or a launch error; a job that moves any of them failed.
REQUIRED_ZERO = (
    "sort_bam.device_parse_fallback",
    "bam.device_inflate_fallback",
    "bam.device_write_fallback",
    "bam.device_deflate_fallback",
    "bam.device_write_tierdown.",
    "flate.lanes_tierdown",
    "flate.lanes_launch_error",
    "flate.deflate_lanes_tierdown",
    "flate.deflate_lanes_launch_error",
    "flate.lockstep_tierdown",
    "flate.lockstep_launch_error",
    "flate.oom_tierdown",
    "bam.oom_tierdown",
)


class NoChip(RuntimeError):
    pass


class OffPath(RuntimeError):
    """The program did not take the cell's path: no result is given."""

    def __init__(self, cell: str, job: str, reasons: List[str]) -> None:
        super().__init__(f"cell {cell}: {job} did not take the cell's path:\n"
                         + "\n".join(f"  - {r}" for r in reasons))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str

    def generator(self):
        g = self.config["generator"]
        return load_module(os.path.join(self.root, "benchmark", "corpus", f"{g}.py"), f"benchmark_corpus_{g}")

    def reference(self):
        r = self.traffic["reference"]
        return load_module(os.path.join(self.root, "benchmark", "references", f"{r}.py"),
                           f"benchmark_reference_{r}")

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.root, "benchmark", "metrics", f"{metric}.py")
        return load_module(path, "benchmark_metric_" + metric.replace(".", "_")).read


def _for_cell(metrics: List[dict], cell: str, reported: List[str]) -> List[dict]:
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m.get("moves", m["name"]) in reported or m["name"] in reported:
            out.append(m)
    return out


def resolve(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic", f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or name in m["workloads"]]
    names = [m["name"] for m in e2e]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=e2e,
        per_layer=_for_cell(bench["per_layer"], name, names),
        root=root,
    )


class CompileWatch:
    """Backend compiles and compile-cache loads, from ``jax.monitoring``."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0
        import jax.monitoring as monitoring

        def on_duration(key: str, secs: float, **_) -> None:
            if "backend_compile" in key or "cache_retrieval" in key:
                self.seconds += secs
                self.count += 1

        monitoring.register_event_duration_secs_listener(on_duration)


def require_chips(chips: int) -> dict:
    """The device JAX sees; raises :class:`NoChip` unless it is a TPU with
    at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(
            f"the cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


@dataclass
class Job:
    start: float
    end: float
    out: str
    report: Optional[dict] = None
    error: str = ""
    reasons: List[str] = field(default_factory=list)  # path rules it broke
    failed: bool = False  # set by the check

    @property
    def wall(self) -> float:
        return self.end - self.start


def command_line(argv: List[str], src: str, out: str) -> List[str]:
    """The traffic mix's ``argv`` with ``{input}`` and ``{output}`` filled
    in."""
    return [src if a == "{input}" else out if a == "{output}" else a for a in argv]


def run_job(src: str, out: str, argv: List[str]) -> Job:
    """One job through the program's CLI; its ``--metrics`` report is the
    JSON object that its output ends with."""
    from hadoop_bam_tpu import cli

    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(command_line(argv, src, out))
    t1 = time.perf_counter()
    text = buf.getvalue()
    if rc != 0:
        return Job(t0, t1, out, error=f"{argv[0]} exited {rc}: {text[-2000:]}")
    return Job(t0, t1, out, report=json.loads(text[0 if text.startswith("{") else text.index("\n{") + 1:]))


def call_job(job_fn: Callable[[str, str, List[str]], Job], src: str, out: str, argv: List[str]) -> Job:
    """``job_fn``'s job; an exception it raises becomes the job's error."""
    t0 = time.perf_counter()
    try:
        return job_fn(src, out, argv)
    except Exception:  # the job failed; the run goes on and reports it
        return Job(t0, time.perf_counter(), out, error=traceback.format_exc()[-4000:])


def path_breaks(job: Job, expect: dict) -> List[str]:
    """Why ``job`` is no run of the cell's path; empty where it is one."""
    return [f"error: {job.error}"] if job.error else failure_reasons(job.report, expect)


def failure_reasons(report: dict, expect: dict) -> List[str]:
    """Why a finished job does not count as a run of the cell's path."""
    counters = report.get("counters", {})
    manifest = report.get("run_manifest", {})
    out = []
    if manifest.get("degraded"):
        out.append(f"run manifest degraded: {manifest.get('reasons')}")
    moved = {
        k: v for k, v in counters.items()
        if v and any(k.startswith(z) if z.endswith(".") else k == z for z in REQUIRED_ZERO)
    }
    if moved:
        out.append(f"fallback or tier-down counters moved: {moved}")
    if expect.get("backend") and manifest.get("backend") != expect["backend"]:
        out.append(f"backend {manifest.get('backend')!r}, not {expect['backend']!r}")
    for k in expect.get("nonzero", []):
        if not counters.get(k):
            out.append(f"counter {k} did not move")
    want = int(expect.get("shuffle_devices", 0))
    if want and counters.get("mh.shuffle.devices", 0) < want:
        out.append(f"shuffle on {counters.get('mh.shuffle.devices', 0)} devices, not {want}")
    return out


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    jobs: List[Job]
    records: int
    trace: object  # trace.Reduced, or None
    work: Dict[str, float]
    setup: Dict[str, float]
    peaks: dict

    def counter(self, name: str) -> float:
        return sum(j.report["counters"].get(name, 0) for j in self.jobs)

    def span_per_job(self, name: str) -> Optional[float]:
        vals = [j.report["span_seconds"].get(name) for j in self.jobs]
        vals = [v for v in vals if v is not None]
        return sum(vals) / len(vals) if vals else None

    def device_s(self, spans) -> Dict[int, float]:
        return self.trace.device_s(spans) if self.trace is not None else {}


def profile_job(job_fn, src: str, out: str, argv: List[str], prof_dir: str):
    """One job under the JAX profiler (host spans and device planes, no
    Python tracer); returns the job and its ``.xplane.pb``."""
    import glob

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(prof_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation("benchmark.job"):
            job = job_fn(src, out, argv)
    return job, glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"), recursive=True)[0]


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _memory_peak() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        try:
            peaks.append(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))
        except Exception:  # a backend without memory stats
            peaks.append(0)
    return max(peaks) if peaks else 0


def _window(job_fn, src: str, work_dir: str, argv: List[str], seconds: float) -> Tuple[float, List[Job]]:
    """Jobs back to back: each starts while the window is open and runs to
    its end, so none is cut and a slow last job slows the rate."""
    w0 = time.perf_counter()
    jobs: List[Job] = []
    while time.perf_counter() < w0 + seconds:
        jobs.append(job_fn(src, os.path.join(work_dir, f"job{len(jobs)}.out"), argv))
    return w0, jobs


def run(
    cell: Cell,
    seed: int,
    seconds: float,
    traced: bool,
    t_process: float,
    device: Optional[dict] = None,
    job_fn: Callable[[str, str, List[str]], Job] = run_job,
) -> dict:
    """One run of ``cell``; returns the result line's object.  Raises
    :class:`OffPath` where the program did not take the cell's path: in
    set-up, before the window opens, where the warm-up job did not."""
    job_fn = functools.partial(call_job, job_fn)
    watch = CompileWatch()
    t0 = time.time()
    if device is None:
        device = require_chips(cell.chips)
    backend_s = time.time() - t0
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else {}
    tr = cell.traffic
    limits = tr["limits"]
    expect = tr.get("expect", {})
    gen, ref = cell.generator(), cell.reference()
    argv = list(tr["argv"])
    work_dir = tempfile.mkdtemp(prefix="benchmark-")
    try:
        t0 = time.time()
        corpus = gen.generate(cell.config, int(tr["records_per_job"]), seed)
        src = os.path.join(work_dir, "input")
        work = gen.write(corpus, src, int(tr["input_level"]))
        corpus_s = time.time() - t0
        t0 = time.time()
        warm = job_fn(src, os.path.join(work_dir, "warmup.out"), argv)
        reasons = path_breaks(warm, expect)
        if reasons:
            raise OffPath(cell.name, "the warm-up job", reasons)
        os.remove(warm.out)
        setup = {
            "backend_s": backend_s, "corpus_s": corpus_s, "warmup_s": time.time() - t0,
            "compile_s": watch.seconds, "compiles": watch.count,
        }
        setup_s = time.time() - t_process
        _log("setup: " + " ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                                  for k, v in setup.items())
             + f" total={setup_s:.3f} (input: {corpus.n_records} records, {work})")
        c_win = watch.count, watch.seconds
        if traced:
            job, xplane = profile_job(job_fn, src, os.path.join(work_dir, "job0.out"), argv,
                                      os.path.join(work_dir, "trace"))
            jobs = [job]
        else:
            w0, jobs = _window(job_fn, src, work_dir, argv, seconds)
        memory_peak = _memory_peak()
        _log(f"window: {len(jobs)} whole jobs, compiles or cache loads in window "
             f"{watch.count - c_win[0]} ({watch.seconds - c_win[1]:.3f} s), walls "
             + " ".join(f"{j.wall:.3f}" for j in jobs))

        # ---- the check, after the window ------------------------------------
        want = ref.expected(corpus, tr)
        worst: Dict[str, float] = {}
        wrong = 0
        rule_breaks: Counter = Counter()
        compared: Dict[bytes, Dict[str, float]] = {}
        for j in jobs:
            data = b""
            if not j.error:
                with open(j.out, "rb") as f:
                    data = f.read()
            j.reasons = path_breaks(j, expect)
            rule_breaks.update(j.reasons)
            # Byte-identical outputs hold the same answer: compare each
            # distinct output once.
            digest = hashlib.blake2b(data, digest_size=16).digest()
            if digest not in compared:
                compared[digest] = ref.compare(data, want)
            got = compared[digest]
            for k, v in got.items():
                worst[k] = max(worst.get(k, v), v)
            over = any(v > limits[k] for k, v in got.items())
            wrong += bool(j.error or over)
            j.failed = bool(over or j.reasons)
        _log(f"check: {len(compared)} distinct output(s) compared with the reference")
        for r, k in rule_breaks.items():
            _log(f"{k} job(s) failed the path rule: {r}")
        # Only jobs that took the cell's path are read: no rate, and no
        # per-layer metric, comes from a fallback.
        on_path = [j for j in jobs if not j.reasons]
        if not on_path:
            raise OffPath(cell.name, "the traced job" if traced else f"every job of the window ({len(jobs)})",
                          [f"{k} job(s): {r}" for r, k in rule_breaks.items()])
        work.update(ref.work(want))
        ctx = Context(on_path, corpus.n_records, None, work, setup, peaks)
        if traced:
            from . import trace as trace_mod

            ctx.trace = trace_mod.reduce(trace_mod.load(xplane))
        result = {"correct": bool(jobs) and not wrong, "attempted": len(jobs),
                  "failed": sum(j.failed for j in jobs)}
        metrics = {}
        if traced:
            for m in cell.per_layer:
                v = cell.reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            # The records of jobs that failed a path rule do not count:
            # a silent fallback reads as a loss, not a gain.
            done = sum(not j.failed for j in jobs)
            metrics[tr["rate_metric"]] = {
                "value": corpus.n_records * done / (jobs[-1].end - w0), "unit": units[tr["rate_metric"]]}
            metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        result["metrics"] = metrics
        dev = dict(device, memory_peak_bytes=memory_peak)
        if traced and ctx.trace is not None:
            dev["busy_s"] = ctx.trace.busy_s()
            dev["window_s"] = ctx.trace.window_s
            result["breakdown"] = {
                "device_ops": ctx.trace.top_runs(), "idle_gaps": ctx.trace.top_gaps()}
        result["device"] = dev
        result["checks"] = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
        return result
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
