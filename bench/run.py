#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: the graph and the engine's layout) and a
traffic mix (``bench/traffic/<mix>.json``: the algorithm and its solve).  One
run:

1. checks that JAX sees the cell's TPU chips (else exits 2, printing no result);
2. keeps JAX's compile cache in $JAX_COMPILATION_CACHE_DIR, else in
   ``<checkout>/.jax_cache``;
3. generates the Graph500 graph on the device from ``--seed`` (gen.py);
4. builds ``PMVEngine`` and ``prepare(spec)`` as the configuration states;
5. warms up with ``run(spec, max_iters=1)``, which compiles the step;
6. ``--trace 0``: runs whole solves through ``PMVEngine.run`` back to back
   until ``--seconds`` have passed, finishing the solve in progress;
   ``--trace 1``: runs one solve under the profiler instead, and keeps the
   compiled step's HLO text beside the profile (``STEP_HLO``);
7. reads ``peak_bytes_in_use`` of every device of the cell, frees the engine,
   and compares every solve's vector with the plain reference (ref.py);
8. prints the result as the last line of standard output: the cell's
   end-to-end metrics with ``--trace 0``, its per-layer metrics (read by
   ``bench/metrics/<name>.py`` from the trace) with ``--trace 1``.  Each number
   compared for ``correct`` is printed with its limit under ``checks``, and
   again as the last lines of standard error.

set-up (``setup_s``) runs from the start of this process to the start of the
window: JAX start-up, graph, prepare, compile or cache load, warm-up.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# run as a script, bench/ is sys.path[0]: drop it so bench/trace.py never
# shadows the standard library's ``trace``, and import ``bench`` as a package.
if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH:
    sys.path.pop(0)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import gen, hlo, ref  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.reading import Reading  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = os.path.join(ROOT, "bench_out", "trace")
# the traced run's compiled step, beside its profile in the trace directory
STEP_HLO = "step.hlo.txt"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Cells, found by name
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None, root: str = ROOT) -> dict:
    """The cell's workload entry, configuration, traffic and metric entries."""
    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# The chip
# ---------------------------------------------------------------------------

def require_chips(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU found (JAX platform is {devices[0].platform!r});"
            " nothing was run")
        sys.exit(2)
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} TPU chips, JAX sees {len(devices)}")
        sys.exit(2)
    return devices[:chips]


def use_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def hub_theta(edges: np.ndarray, n: int, hubs: int) -> float:
    """θ at the out-degree of the ``hubs``-th largest vertex."""
    out_deg = np.bincount(edges[:, 0], minlength=n)
    return float(np.partition(out_deg, n - hubs)[n - hubs])


def make_spec(traffic: dict, n: int):
    from repro.core import connected_components, pagerank

    if traffic["algorithm"] == "pagerank":
        return pagerank(n, traffic["damping"])
    if traffic["algorithm"] == "wcc":
        return connected_components()
    raise SystemExit(f"unknown algorithm {traffic['algorithm']!r}")


def build_engine(config: dict, edges: np.ndarray, n: int, devices: list, **extra):
    from repro.core import PMVEngine

    lay = config["layout"]
    kw = dict(b=lay["blocks"], strategy=lay["strategy"], exchange=lay["exchange"],
              backend=lay["backend"])
    if lay["theta"] is not None:
        kw["theta"] = hub_theta(edges, n, lay["theta"]["hubs"])
    if lay["mesh"] is not None:
        from repro.core.mesh import worker_mesh

        kw["mesh"] = worker_mesh(lay["workers"], lay["mesh"], devices=devices)
    kw.update(extra)
    return PMVEngine(edges, n, **kw)


def departures(config: dict, meta: dict, matrix, chips: int,
               require_compiled: bool) -> list[str]:
    """Where the prepared engine is not what the configuration states."""
    lay, out = config["layout"], []
    if meta["backend"] != lay["expect_backend"]:
        out.append(f"backend {meta['backend']!r} (expected {lay['expect_backend']!r})")
    if require_compiled and meta["cfg"].interpret:
        out.append("Pallas kernels in interpret mode")
    if meta.get("exchange") != lay["exchange"]:
        out.append(f"exchange {meta.get('exchange')!r} (expected {lay['exchange']!r})")
    if lay["dense_region"] and not (meta["n_dense"] > 0 and "dense_matrix" in matrix):
        out.append("the dense region is empty or not on the dense kernel")
    if lay["mesh"] is not None:
        spans = {len(x.sharding.device_set) for x in jax.tree.leaves(matrix)
                 if hasattr(x, "sharding")}
        if spans != {chips}:
            out.append(f"matrix leaves span {sorted(spans)} devices, not {chips}")
    return out


def step_hlo(eng, spec) -> str | None:
    """The optimized HLO text of the engine's compiled step, loaded again
    from the compile cache; None for the step with delta-iteration state,
    which takes 5 arguments."""
    step, matrix, v0, ctx, mask, meta = eng.prepare(spec)
    if meta["cfg"].delta_eps is not None:
        return None
    return step.lower(matrix, v0, ctx, mask).compile().as_text()


def tree_bytes(tree) -> int:
    return int(sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree)))


def peak_bytes(devices: list) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]


class GcPauses:
    """Python's garbage collections, with their pauses, while ``on``."""

    def __init__(self):
        self.on, self.pauses, self._t = False, [], 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))


class CompileCounter:
    """Programs compiled, or loaded from the compile cache, so far."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1


COMPILES = CompileCounter()
GC_PAUSES = GcPauses()


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices: list, *,
             require_compiled: bool = True, t_start: float = T_START,
             trace_dir: str = TRACE_DIR, peaks_table: dict | None = None,
             engine_kw: dict | None = None) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result.
    ``engine_kw`` adds engine options the configuration does not state (the
    lower-precision control of control.py)."""
    config, traffic = cell["config"], cell["traffic"]
    name = cell["workload"]["name"]
    chips = len(devices)
    compiles = COMPILES

    t = time.perf_counter()
    edges, n = gen.graph500(config["scale"], config["edgefactor"], config["rmat"]["a"],
                            config["rmat"]["b"], config["rmat"]["c"],
                            config["graph_seed"], seed, config["layout"]["blocks"])
    graph_s = time.perf_counter() - t
    m = len(edges)
    log(f"[graph] graph500-{config['scale']} seed={seed} n={n} edge_slots={m}"
        f" graph_s={graph_s:.3f} peak_after_graph={peak_bytes(devices)}")

    spec = make_spec(traffic, n)
    t = time.perf_counter()
    eng = build_engine(config, edges, n, devices, **(engine_kw or {}))
    log(f"[engine] engine_s={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    _step, matrix, _v0, _ctx, _mask, meta = eng.prepare(spec)
    prepare_s = time.perf_counter() - t
    matrix_bytes = tree_bytes(matrix)
    off = departures(config, meta, matrix, chips, require_compiled)
    del _step, matrix, _v0, _ctx, _mask
    plan = meta.get("plan")
    log(f"[prepare] prepare_s={prepare_s:.3f} backend={meta['backend']}"
        f" interpret={meta['cfg'].interpret} strategy={meta['strategy']}"
        f" exchange={meta.get('exchange')} theta={meta['theta']}"
        f" dense_vertices={meta['n_dense']} matrix_bytes={matrix_bytes}"
        f" tactics={plan.tactic_counts() if plan is not None else None}")
    for d in off:
        log(f"[prepare] departs from the configuration: {d}")

    run_kw = dict(max_iters=traffic["max_iters"], tol=traffic["tol"])
    t = time.perf_counter()
    eng.run(spec, max_iters=1, tol=traffic["tol"])
    log(f"[warm-up] first call (compile or cache load, 1 iteration)"
        f" {time.perf_counter() - t:.3f} s, programs compiled so far"
        f" {compiles.count}")

    solves = []  # (iterations, converged, fell back, vector)
    solve_s = []
    compiled_before = compiles.count
    GC_PAUSES.on, GC_PAUSES.pauses = True, []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                res = eng.run(spec, **run_kw)
        finally:
            jax.profiler.stop_trace()
        solves.append((res.iterations, res.converged, "fallback" in res.totals, res.v))
    else:
        ts = t0
        while True:
            res = eng.run(spec, **run_kw)
            solves.append((res.iterations, res.converged, "fallback" in res.totals,
                           res.v))
            now = time.perf_counter()
            solve_s.append(now - ts)
            ts = now
            if now - t0 >= seconds:
                break
    t1 = time.perf_counter()
    GC_PAUSES.on = False
    in_window = compiles.count - compiled_before
    peaks = peak_bytes(devices)
    iters = sum(s[0] for s in solves)
    log(f"[window] solves={len(solves)} iterations={iters} window_s={t1 - t0:.3f}"
        f" setup_s={setup_s:.3f} programs_compiled_in_window={in_window}"
        f" peak_bytes_in_use={peaks}")
    log(f"[window] start_unix={time.time() - (time.perf_counter() - t0):.3f}"
        f" solve_s={[round(x, 4) for x in solve_s]}"
        f" gc_collections={len(GC_PAUSES.pauses)}"
        f" gc_pause_s={sum(p for _, p in GC_PAUSES.pauses):.4f}"
        f" longest_gc={max(GC_PAUSES.pauses, key=lambda g: g[1], default=None)}")

    step_text = step_hlo(eng, spec) if trace else None
    kinds, step_module = ((hlo.op_kinds(step_text), hlo.module_name(step_text))
                          if step_text else ({}, None))
    if step_text:
        with open(os.path.join(trace_dir, STEP_HLO), "w") as f:
            f.write(step_text)
    del eng, res
    gc.collect()
    jax.clear_caches()

    result_metrics: dict = {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": max(peaks)}
    breakdown = None
    if trace:
        profile = tr.load(tr.find_xplane(trace_dir))
        host = tr.host_events(profile)
        lo, hi = tr.window(host)
        modules = tr.module_events(profile)
        step = ([e for e in modules if e.name == step_module]
                if modules and step_module else None)
        names = sorted({e.name for e in modules if e.start < hi and e.end > lo})
        log(f"[trace] programs run in the window: {names}; step module"
            f" {step_module!r}, {len(step or [])} runs")
        reading = Reading(ops=tr.device_ops(profile), host=host, lo=lo, hi=hi,
                          iterations=solves[0][0], chips=chips, n=n, edge_slots=m,
                          weighted=spec.needs_weights,
                          peaks=peaks_table or load_peaks(device["kind"]),
                          prepare_s=prepare_s, matrix_bytes=matrix_bytes, hlo=kinds,
                          step=step)
        for entry in cell["per_layer"]:
            value = metric_reader(entry["name"])(reading)
            if value is not None:
                result_metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        device["busy_s"] = reading.busy_s()
        device["window_s"] = reading.window_s()
        breakdown = tr.breakdown(reading.ops, host, lo, hi, kinds=kinds)
    else:
        e2e = {
            "edges_per_s": (m * iters / (t1 - t0), "edges/s"),
            "peak_bytes_per_edge": (sum(peaks) / m, "B/edge"),
            "setup_s": (setup_s, "s"),
        }
        for entry in cell["end_to_end"]:
            value, unit = e2e[entry["name"]]
            result_metrics[entry["name"]] = {"value": value, "unit": unit}

    # ---- correct: every solve of the window against the plain reference
    t = time.perf_counter()
    want = ref.reference(traffic, edges, n)
    per_solve = [ref.compare(traffic, s[3], want) for s in solves]
    check = traffic["check"]
    # tol 0 runs every iteration; a positive tol has to converge
    unfinished = [(it != traffic["max_iters"]) if traffic["tol"] <= 0 else not conv
                  for it, conv, _, _ in solves]
    fallbacks = sum(1 for s in solves if s[2])
    checks = {
        check["name"]: {"value": max(per_solve), "limit": check["limit"]},
        "unfinished_solves": {"value": sum(unfinished), "limit": 0},
        "fallbacks": {"value": fallbacks, "limit": 0},
        "config_departures": {"value": len(off), "limit": 0},
    }
    failed = sum(1 for k, s in enumerate(solves)
                 if per_solve[k] > check["limit"] or s[2] or unfinished[k])
    correct = bool(solves) and all(c["value"] <= c["limit"] for c in checks.values())
    log(f"[reference] {time.perf_counter() - t:.3f} s; per-solve {check['name']}:"
        f" {per_solve}")

    result = {"correct": correct, "attempted": len(solves), "failed": failed,
              "metrics": result_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for key, c in checks.items():
        log(f"check {key} = {c['value']!r} (limit {c['limit']!r})")
    log(f"[{name}] correct={correct}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    cell = load_cell(args.workload)
    t = time.perf_counter()
    devices = require_chips(cell["workload"]["chips"])
    devices_s = time.perf_counter() - t
    import repro  # noqa: F401  (fails here when the program is absent)

    use_compile_cache()
    log(f"[device] platform={devices[0].platform} kind={devices[0].device_kind}"
        f" count={len(devices)} devices_s={devices_s:.3f}"
        f" since_start_s={time.perf_counter() - T_START:.3f}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
