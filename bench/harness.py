"""One benchmark run: set up, warm up, measure, check, print the result.

``run.py`` parses the command line; everything else is here, driven by
``BENCHMARK.json`` and the files it names:

* the cell's configuration, ``bench/configs/<file>``: every field of the
  program's configuration dataclasses, plus the deployment it stands for
  (its ``stations``, the ``channels`` each records, default 1, and the
  ``layout`` module its streams follow, default ``bench/layouts/network.py``;
  a ``layout`` path that does not exist fails the set-up);
* its traffic mix, ``bench/traffic/<traffic>.json`` (``traffic.py``): the
  events, which the generator lays over the configuration's stations;
* each metric's reader, ``bench/metrics/<name>.py`` (or the reader of
  the name before its first dot), which returns a number or None;
* the limits of the comparison, ``bench/limits/<cell>.json`` if present,
  else ``bench/limits/default.json``.

The window drives ``StreamingDetector.push`` with one network chunk per
push, closed loop: the pooled fused step (one dispatch), one
``device_get``, the host tail, and the ``poll_detections`` that ``push``
runs. Set-up builds the detector with frozen statistics, then pushes the
first chunks until three blocks have gone through both step entries, so
every program the window runs is compiled (or loaded from the persistent
cache) before it starts, and makes the window's chunks. After the window
every stream's output is compared with the plain reference.

The stream layout. The program's pool holds one member per stream: the
configuration's stations, each with its components, station-major and
component-minor (stream ``i`` is component ``i % channels`` of station
``i // channels`` in the default layout). A layout module provides

* ``streams(conf)``: the station of each stream;
* ``make_stream(conf, mix, seed, lag, chunk_samples)``: the seeded
  stream, whose ``chunk(k)`` and ``span(n)`` are ``(streams, samples)``;
* ``frozen_stats(conf, mix, stream)``: (median, MAD) per stream;
* ``make_detector(cfg, scfg, med, mad)``: the detector, through
  ``program``;
* ``install_taps(det, annotate)``: the taps the check reads;
* ``processed(det)``: the fingerprints each stream has put through the
  step;
* ``compare(conf, stream, stats, n_fp, pk_rows, taps, overflow,
  limits)``: the checks, each ``{"value", "limit"}``.

Everything else is here. Of what the metric readers get (``ctx``),
``stations`` and ``station_s`` count the configuration's stations, so a
station of three components is one station-hour an hour; ``blocks``
counts one stream's blocks (every stream advances together); ``work`` is
one step's work of the streams on the busiest chip.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
WARMUP_BLOCKS = 3
DEFAULT_LAYOUT = "bench/layouts/network.py"


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# specification lookup
# ---------------------------------------------------------------------------


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(spec: dict, workload: str, root: pathlib.Path = ROOT):
    """(cell, configuration dict) of workload ``workload``."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())
    return cell, conf


def metrics_of(spec: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, directory: pathlib.Path = BENCH / "metrics"):
    """The ``read(ctx)`` function of metric ``name``."""
    for stem in (name, name.split(".")[0]):
        path = directory / f"{stem}.py"
        if path.exists():
            return _load(path, f"bench_metric_{stem.replace('.', '_')}").read
    raise FileNotFoundError(f"no reader for metric {name!r} in {directory}")


def layout_of(conf: dict, root: pathlib.Path = ROOT):
    """The layout module the configuration names, relative to the root of
    the checkout; never a fallback for a path that is not there."""
    rel = conf.get("layout", DEFAULT_LAYOUT)
    path = root / rel
    if not path.is_file():
        raise FileNotFoundError(f"configuration {conf.get('name')!r} names "
                                f"the layout {rel!r}, which does not exist")
    return _load(path, f"bench_layout_{path.stem}")


def limits_for(workload: str, directory: pathlib.Path = BENCH / "limits"
               ) -> dict:
    path = directory / f"{workload}.json"
    if not path.exists():
        path = directory / "default.json"
    return json.loads(path.read_text())


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": chips}
    log(f"device platform={d.platform} device_kind={d.device_kind!r} "
        f"visible={len(devs)} used={chips}")
    if require_tpu and d.platform != "tpu":
        raise NoAccelerator(f"no TPU visible (default device is "
                            f"{d.platform}); the benchmark never runs on "
                            f"the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return info


def memory_peak(jax, chips: int) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class WindowWatch:
    """What ran besides the work while ``on`` is set: the tracing,
    compilation and compile-cache loads JAX reports (the window should
    see none) and the interpreter's garbage collections, each with the
    push it fell in."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.on = False
        self.push = 0
        self.compiles: list = []
        self.collections: list = []
        self._t = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        gc.callbacks.append(self._collect)

    def _listen(self, event, duration, **kwargs):
        if self.on and event in self.EVENTS:
            self.compiles.append((event.rsplit("/", 1)[-1],
                                  kwargs.get("fun_name"), duration))

    def _collect(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.collections.append((info["generation"], self.push,
                                     time.perf_counter() - self._t))

    def close(self) -> None:
        self.on = False
        if self._collect in gc.callbacks:
            gc.callbacks.remove(self._collect)

    def summary(self) -> str:
        full = [c for c in self.collections if c[0] == 2]
        longest = max(self.collections, key=lambda c: c[2], default=None)
        return (f"traced or compiled inside the window: "
                f"{json.dumps(self.compiles)}; garbage collections "
                f"{len(self.collections)} ({len(full)} full), "
                f"{sum(c[2] for c in self.collections):.4f}s in all, "
                f"longest (generation, push, s) {longest}")


def _annotate(jax, traced: bool):
    if traced:
        return jax.profiler.TraceAnnotation
    return lambda name, **kw: contextlib.nullcontext()


def run_cell(cell: dict, conf: dict, mix, seed: int, seconds: float,
             traced: bool, metrics: list[dict], limits: dict, *,
             t_start: float, precision: str | None = None,
             require_tpu: bool = True, fault=None) -> dict:
    """Set up, warm up, measure for ``seconds``, check; returns the
    result line's object. ``fault`` (tests only) wraps the program's pool
    step entries before the taps do, to break the timed path underneath
    what the check reads."""
    import jax
    from bench import program, reference, work

    layout = layout_of(conf)
    chips = int(cell["chips"])
    info = device_info(jax, chips, require_tpu)
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = WindowWatch(jax)
    program.set_precision(precision or conf.get("matmul_precision",
                                                "highest"))
    cfg, scfg = program.build(conf)
    fp, lsh, idx = conf["fingerprint"], conf["lsh"], conf["index"]
    nb = scfg.block_fingerprints
    lag = reference.lag_samples(fp)
    fs = fp["fs"]
    n_st = int(conf["stations"])
    n_streams = layout.streams(conf).size
    stream = layout.make_stream(conf, mix, seed, lag, nb * lag)

    t0 = time.perf_counter()
    med, mad = layout.frozen_stats(conf, mix, stream)
    log(f"frozen statistics for {n_streams} streams of {n_st} stations "
        f"over {mix.stats_fingerprints} fingerprints: "
        f"{time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    det = layout.make_detector(cfg, scfg, med, mad)
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(det.pstate.index))
    log(f"detector: {n_streams} streams, pool state {state_bytes} bytes, "
        f"mesh {None if det.mesh is None else det.mesh.devices.size}: "
        f"{time.perf_counter() - t0:.2f}s")
    held = len(layout.processed(det))
    if held != n_streams:
        raise ValueError(f"the layout's detector holds {held} streams, its "
                         f"layout names {n_streams}")
    annotate = _annotate(jax, traced)
    if fault is not None:
        fault()
    taps = layout.install_taps(det, annotate)
    poll_orig = det.poll_detections
    poll_s = [0.0, 0]

    def poll():
        t = time.perf_counter()
        with annotate("bench.poll"):
            out = poll_orig()
        poll_s[0] += time.perf_counter() - t
        poll_s[1] += 1
        return out

    det.poll_detections = poll
    if traced:
        # the program's spans also become trace annotations, so an idle
        # gap on the device is labelled by the host stage it falls in
        span_orig = det.telemetry.tracer.span

        @contextlib.contextmanager
        def span(name, **attrs):
            with annotate(f"bench.span.{name}"), span_orig(name, **attrs) as s:
                yield s

        det.telemetry.tracer.span = span
    try:
        t0 = time.perf_counter()
        k = 0
        warm_s = []
        while layout.processed(det)[0] < WARMUP_BLOCKS * nb:
            chunk = stream.chunk(k)
            ts = time.perf_counter()
            det.push(chunk)
            warm_s.append(time.perf_counter() - ts)
            k += 1
        # the window's chunks, made before it opens: enough for half as
        # many again as pushes at the pace of the last warm-up push
        t1 = time.perf_counter()
        ahead = [stream.chunk(k + i)
                 for i in range(int(1.5 * seconds / warm_s[-1]) + 2)]
        log(f"warm-up: {k} pushes, {layout.processed(det)[0]} "
            f"fingerprints per stream, pushes {json.dumps(warm_s)}: "
            f"{t1 - t0:.2f}s; {len(ahead)} chunks made for the window: "
            f"{time.perf_counter() - t1:.2f}s")
        tracer = det.telemetry.tracer
        spans0 = {name: tuple(v) for name, v in tracer.totals.items()}
        fp0 = layout.processed(det)[0]
        poll0 = tuple(poll_s)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        setup_s = time.perf_counter() - t_start
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        push_s = []
        late = 0
        # what set-up built is never garbage again: a full collection in
        # the window walks only what the window made
        gc.freeze()
        watch.on = True
        t_win = time.perf_counter()
        t_end = t_win
        while t_end - t_win < seconds:
            watch.push = len(push_s)
            if len(push_s) < len(ahead):
                chunk = ahead[len(push_s)]
            else:
                chunk = stream.chunk(k + len(push_s))
                late += 1
            ts = time.perf_counter()
            with annotate("bench.push"):
                det.push(chunk)
            t_end = time.perf_counter()
            push_s.append(t_end - ts)
        window_s = t_end - t_win
        watch.close()
        gc.unfreeze()
        if traced:
            jax.profiler.stop_trace()
        peak = memory_peak(jax, chips)
        del ahead
        spans = {name: (v[0] - spans0.get(name, (0, 0.0))[0],
                        v[1] - spans0.get(name, (0, 0.0))[1])
                 for name, v in tracer.totals.items()}
        spans["poll"] = (poll_s[1] - poll0[1], poll_s[0] - poll0[0])
        spans["push"] = (len(push_s), float(sum(push_s)))
        blocks = (layout.processed(det)[0] - fp0) // nb
        overflow = det.telemetry.drop_breakdown()["overflow_pairs"]
        slow = sorted(range(len(push_s)), key=push_s.__getitem__)[-3:]
        log(f"window: {len(push_s)} pushes, {blocks} blocks, "
            f"{window_s:.3f}s; slowest pushes "
            f"{[(i, round(push_s[i], 4)) for i in reversed(slow)]}; "
            f"{late} chunks made inside the window; {watch.summary()}; spans "
            f"{json.dumps(spans)}; drops "
            f"{json.dumps(det.telemetry.drop_breakdown())}; memory peak "
            f"{peak} bytes")

        # work of one step on the busiest chip: its streams, each a
        # station's step
        per_chip = -(-n_streams // chips)
        win_rows = [r for rows in taps.pairs.values() for r in rows
                    if r[0] >= len(taps.jac) - blocks]
        pairs_per = (sum(r[1].size for r in win_rows)
                     / max(1, blocks * n_streams))
        log(f"max pairs in one block of a stream: "
            f"{max((r[1].size for r in win_rows), default=0)} of "
            f"{scfg.max_pairs_per_block}")
        one = work.station_step(fp, lsh, idx, nb, pairs_per)
        step_work = {name: v * per_chip for name, v in one.items()}

        trace_red = None
        if traced:
            from bench import trace as trace_mod
            t0 = time.perf_counter()
            tr = trace_mod.load(trace_mod.find_xplane(trace_dir))
            win = [(a, b) for name, a, b in tr.annotations
                   if name == "bench.push"]
            window = (win[0][0], win[-1][1]) if win else None
            trace_red = trace_mod.reduce_trace(tr, window)
            shutil.rmtree(trace_dir, ignore_errors=True)
            log(f"trace: {time.perf_counter() - t0:.2f}s to reduce; step "
                f"program {trace_red['step_module']!r} "
                f"x{trace_red['step_executions']}; busy "
                f"{json.dumps(trace_red['busy_s'])} of "
                f"{trace_red['window_s']:.4f}s; idle by host activity "
                f"{json.dumps(trace_red['idle_by_label'])}")
        ctx = {"setup_s": setup_s, "window_s": window_s, "push_s": push_s,
               "blocks": blocks, "stations": n_st, "chips": chips,
               "station_s": n_st * blocks * nb * lag / fs, "spans": spans,
               "trace": trace_red, "work": step_work,
               "device_kind": info["kind"]}
        if traced:
            pk = work.peaks(info["kind"])
            t_min, bound = work.least_time(step_work, pk)
            log(f"step work per chip ({per_chip} streams): "
                f"{step_work['flops']:.6g} FLOPs, {step_work['bytes']:.6g} "
                f"bytes, {step_work['compares']:.6g} Min-Max compares (no "
                f"published peak, not in the bound); least time "
                f"{t_min * 1e3:.6g} ms, {bound}-bound")
        values = {}
        for m in metrics:
            v = reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}

        # what the timed path produced, read back before the state goes
        n_fp = layout.processed(det)
        pk_rows = [np.asarray(det.pstate.index.pk[i, :n])
                   for i, n in enumerate(n_fp)]
        del det, poll, poll_orig
        program.Taps.uninstall()
        gc.collect()
        t0 = time.perf_counter()
        checks = layout.compare(conf, stream, (med, mad), n_fp, pk_rows,
                                taps, overflow, limits)
        log(f"reference check of all {n_streams} streams: "
            f"{time.perf_counter() - t0:.2f}s")
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        device = dict(info, memory_peak_bytes=peak)
        if traced:
            device["busy_s"] = trace_red["busy_s_mean"]
            device["window_s"] = trace_red["window_s"]
        result = {"correct": bool(correct), "attempted": len(push_s),
                  "failed": 0, "metrics": values, "device": device}
        if traced:
            result["breakdown"] = {"device_ops": trace_red["top_ops"],
                                   "idle_gaps": trace_red["idle_gaps"]}
        result["checks"] = checks
        return result
    finally:
        watch.close()
        gc.unfreeze()
        program.Taps.uninstall()


def main(argv: list[str], t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--precision", choices=("highest", "high"),
                    help="matmul precision of the fingerprint chain "
                         "(control runs only; default: the configuration's)")
    args = ap.parse_args(argv)
    spec = load_spec()
    cell, conf = cell_parts(spec, args.workload)
    from bench import traffic
    mix = traffic.load_mix(cell["traffic"])
    try:
        res = run_cell(cell, conf, mix, args.seed, args.seconds,
                       bool(args.trace),
                       metrics_of(spec, args.workload, bool(args.trace)),
                       limits_for(args.workload), t_start=t_start,
                       precision=args.precision)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0
