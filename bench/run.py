"""Run one benchmark cell once on the chip and print one JSON result line.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the configuration's design, builds its group structure and
one ``Plan``, and makes the run's responses from the seed in two
sub-streams (``bench.data``).  It warms up by solving responses of the
warm-up stream through the same call the window makes, so that the bucket
shapes they meet are compiled, or read from the program's persistent
compile cache: ``warmup_responses`` of them, and more, up to
``warmup_max``, while the last one still missed the cache.  The first run
in a checkout thus fills the cache with the shapes that responses commonly
meet, and a later run reads them.  The window then solves fresh responses
of the window stream, none of them seen in set-up, each call building a
fresh ``Problem`` and ``SGLSession`` and running ``.path`` or ``.cv``,
until ``--seconds`` have passed; the call running at the deadline finishes
and counts.  A shape that only a window response meets compiles inside
the window, and ``window_compiles`` / ``window_compile_s`` report it.
After the window, every row the window returned is held to the float64
reference of the configuration's penalty and to the engine's own
``n_uncertified`` count.

``--trace 0`` prints the end-to-end metrics.  ``--trace 1`` profiles a
slice of the window's start (``trace_seconds`` of the traffic file) and
prints the per-layer metrics, the device's busy time in the slice and a
breakdown.  ``--control bf16`` (never used by a timed run) hands the
program bfloat16-rounded inputs, which the check must refuse.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CONTROLS = ("bf16",)
WINDOW_SPAN = "bench.window"
# Rows of B (float64) the reference holds at once: 256 MiB.
REFERENCE_ELEMENTS = 2**25


class NoChip(RuntimeError):
    pass


def _prepare_jax():
    """The program's compile cache, holding every compile of the run."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Backend compiles (persistent-cache reads included) seen by JAX."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.count, self.hits, self.seconds = 0, 0, 0.0
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on_event)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def close(self):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)
        mon.unregister_event_listener(self._on_event)


@dataclasses.dataclass
class Unit:
    """One window call: a certified path or K-fold CV for one response."""
    resp: int
    wall: float
    setup: float
    screen: float
    solve: float
    iters: int
    kept: np.ndarray            # (J,) or (K, J) solver columns per row
    uncertified: int
    lambdas: np.ndarray
    betas: np.ndarray           # (J, p) or (K, J, p)
    folds: list | None = None   # [(train, val)] for CV


@dataclasses.dataclass
class RunData:
    """What the per-layer readers read."""
    cell: object
    units: list
    window_compiles: int
    n_features: int
    device_kind: str
    trace: object = None        # trace_reduce.Summary of the traced slice
    window_compile_s: float = 0.0


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _plan(cell, seed: int):
    from repro.core import Plan
    from bench.data import rng_for
    plan = Plan(**cell.traffic["plan"])
    if cell.task == "cv":
        plan = plan.with_(seed=int(rng_for(seed, 2).integers(0, 2**31 - 1)))
    return plan


def _harvest(res, task: str, k: int, wall: float) -> Unit:
    if task == "path":
        return Unit(resp=k, wall=wall, setup=res.setup_time,
                    screen=res.screen_time, solve=res.solve_time,
                    iters=int(np.sum(res.iters)),
                    kept=np.asarray(res.kept_features),
                    uncertified=int(res.stats.n_uncertified),
                    lambdas=np.asarray(res.lambdas),
                    betas=np.asarray(res.betas))
    return Unit(resp=k, wall=wall, setup=res.setup_time,
                screen=res.screen_time, solve=res.solve_time,
                iters=int(np.sum(res.fold_iters)),
                kept=np.asarray(res.kept_features),
                uncertified=int(res.stats.n_uncertified),
                lambdas=np.asarray(res.lambdas),
                betas=np.asarray(res.fold_betas), folds=list(res.folds))


def _round_bf16(a):
    import jax.numpy as jnp
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def run_cell(cell, seed: int, seconds: float, trace: bool = False,
             control: str | None = None, require_tpu: bool = True) -> dict:
    """Set up, warm up, run the window, check; returns the result dict."""
    import jax
    devices = jax.devices()
    chips = int(cell.traffic["chips"])
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise NoChip(f"cell {cell.name} needs {chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform!r} "
                     f"device(s)")
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    from repro.core import SGLSession
    from bench import catalog, data

    counter = CompileCounter()
    try:
        cfg, task, tr = cell.config, cell.task, cell.traffic
        pen = catalog.penalty(cell.penalty)
        design = data.make_design(cfg)
        sizes = design.sizes
        structure = pen.structure(sizes)
        plan = _plan(cell, seed)
        block = int(tr["window_responses"])
        n_warm = int(tr["warmup_responses"])
        warm = data.make_responses(cfg, design, seed, data.WARMUP, 0,
                                   int(tr.get("warmup_max", n_warm)))
        fresh = data.make_responses(cfg, design, seed, data.WINDOW, 0,
                                    block)
        X_in = design.X
        lower = _round_bf16 if control == "bf16" else (lambda a: a)
        X_in = lower(X_in)

        def call(y, k: int) -> Unit:
            t0 = time.perf_counter()
            c0, s0 = counter.count, counter.seconds
            with _annotate("bench.prep"):
                y = lower(y)
            with _annotate("bench.construct"):
                sess = SGLSession(pen.problem(X_in, y, structure))
            with _annotate("bench.call"):
                res = sess.path(plan) if task == "path" else sess.cv(plan)
            with _annotate("bench.harvest"):
                u = _harvest(res, task, k, time.perf_counter() - t0)
            if counter.count > c0:
                _log(f"call {k}: {counter.count - c0} compiles "
                     f"({counter.seconds - s0:.3f} s), buckets "
                     f"{res.stats.buckets}")
            return u

        _log(f"design made at {time.perf_counter() - T_PROCESS:.3f} s")
        for k, y in enumerate(warm):
            misses = counter.count - counter.hits
            u = call(y, k)
            misses = counter.count - counter.hits - misses
            _log(f"warm-up call {k}: {u.wall:.3f} s; {counter.count} "
                 f"compiles, {counter.hits} cache hits so far")
            if k + 1 >= n_warm and misses == 0:
                break
        del warm
        setup_s = time.perf_counter() - T_PROCESS
        _log(f"set-up {setup_s:.3f} s")

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        compiles0, compile_s0 = counter.count, counter.seconds
        units = []
        with contextlib.ExitStack() as stack:
            if trace:
                stack.enter_context(TraceSlice(
                    trace_dir, float(tr["trace_seconds"])))
            t_win = time.perf_counter()
            while not units or time.perf_counter() - t_win < seconds:
                k = len(units)
                # the window outran set-up's responses: one more block of
                # the same size, so the generator's program is reused
                if k == len(fresh):
                    _log(f"window: making responses {k}..{k + block - 1}")
                    fresh += data.make_responses(cfg, design, seed,
                                                 data.WINDOW, k, block)
                units.append(call(fresh[k], k))
            window_s = time.perf_counter() - t_win
        window_compiles = counter.count - compiles0
        window_compile_s = counter.seconds - compile_s0
        _log(f"window: {len(units)} calls in {window_s:.3f} s, "
             f"{window_compiles} compiles ({window_compile_s:.3f} s)")
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())
        dev = devices[0]
        summary = None
        if trace:
            from bench import trace_reduce
            t_tr = time.perf_counter()
            try:
                summary = trace_reduce.summarize(trace_dir)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            _log(f"trace read in {time.perf_counter() - t_tr:.3f} s")
        t_ref = time.perf_counter()
        X_host = np.asarray(design.X)
        Y_host = np.stack([np.asarray(y) for y in fresh[:len(units)]])
        del X_in, fresh, design
        checks, unit_ok = _check(cell, pen, units, X_host, Y_host, sizes)
        _log(f"reference check {time.perf_counter() - t_ref:.3f} s")
    finally:
        counter.close()

    run = RunData(cell=cell, units=units, window_compiles=window_compiles,
                  n_features=int(X_host.shape[1]),
                  device_kind=dev.device_kind, trace=summary,
                  window_compile_s=window_compile_s)
    metrics = (_per_layer(run) if trace
               else _end_to_end(cell, units, window_s, setup_s, peak))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(c["value"] is not None
                             and c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(units),
              "failed": int(sum(not ok for ok in unit_ok)),
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result


class TraceSlice:
    """Profiles the first ``seconds`` of the window from a thread of its
    own, so the slice ends on time even inside a call: one call of a cell
    can hold millions of device ops, more than a trace can hold."""

    def __init__(self, trace_dir: str, seconds: float):
        self.trace_dir, self.seconds = trace_dir, seconds
        self.error = None
        self._thread = threading.Thread(target=self._slice)

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._thread.start()
        return self

    def _slice(self):
        import jax
        try:
            with _annotate(WINDOW_SPAN):
                time.sleep(self.seconds)
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception as exc:  # reported on the main thread
                self.error = exc

    def __exit__(self, *exc):
        self._thread.join()
        if self.error is not None and exc[0] is None:
            raise RuntimeError("the profiler failed to stop") from self.error
        return False


def _check(cell, pen, units, X, Y, sizes):
    """Hold every row of every window call to the f64 reference of the
    cell's penalty, all calls' rows at once, so each pass over X serves
    many rows.  ``Y[u.resp]`` is the response of window call ``u``."""
    plan = cell.traffic["plan"]
    kw = dict(tol=float(plan["tol"]), sizes=sizes, plan=plan)
    worst = np.zeros(len(units))
    if cell.task == "path":
        problems = [(X, [(i, Y[u.resp], u.betas)
                         for i, u in enumerate(units)])]
    else:
        folds = units[0].folds
        if any(not all(np.array_equal(a[0], b[0]) for a, b in
                       zip(u.folds, folds)) for u in units):
            raise RuntimeError("the window's CV calls used different folds")
        problems = [(X[train], [(i, Y[u.resp][train], u.betas[k])
                                for i, u in enumerate(units)])
                    for k, (train, _) in enumerate(folds)]
    J = len(units[0].lambdas)
    per_batch = max(1, REFERENCE_ELEMENTS // (X.shape[1] * J))
    for Xk, rows in problems:
        for b0 in range(0, len(rows), per_batch):
            batch = rows[b0:b0 + per_batch]
            ratios = pen.gap_ratios(
                Xk, np.concatenate([np.broadcast_to(y, (J, len(y)))
                                    for _, y, _ in batch]),
                np.concatenate([units[i].lambdas for i, _, _ in batch]),
                np.concatenate([B for _, _, B in batch]), **kw)
            for j, (i, _, _) in enumerate(batch):
                worst[i] = max(worst[i], ratios[j * J:(j + 1) * J].max())
    limit = float(cell.traffic["gap_limit"])
    unc = np.array([u.uncertified for u in units])
    unit_ok = (worst <= limit) & (unc == 0)
    w = float(worst.max())
    checks = {"worst_gap_over_tol": {"value": w if np.isfinite(w) else None,
                                     "limit": limit},
              "n_uncertified": {"value": int(unc.sum()), "limit": 0}}
    return checks, unit_ok


def _end_to_end(cell, units, window_s, setup_s, peak) -> dict:
    per_unit = window_s / len(units)
    out = {"setup_s": {"value": setup_s, "unit": "s"},
           f"{cell.task}_s": {"value": per_unit, "unit": "s"},
           "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"}}
    return out


def _per_layer(run: RunData) -> dict:
    from bench import catalog
    out = {}
    for name in catalog.metric_names():
        mod = catalog.metric(name)
        if not catalog.applies(mod, run.cell):
            continue
        value = mod.read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=CONTROLS, default=None,
                    help="lower the inputs' precision (correctness control)")
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.core  # noqa: F401
    except ImportError as exc:
        print(f"bench.run needs the program (src/repro) beside it: {exc}",
              file=sys.stderr)
        return 2
    _prepare_jax()
    from bench import catalog
    try:
        cell = catalog.cell(args.workload)
    except KeyError as exc:
        print(f"unknown workload {args.workload!r}: {exc}", file=sys.stderr)
        return 2
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          args.control)
    except NoChip as exc:
        print(f"bench.run: {exc}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
