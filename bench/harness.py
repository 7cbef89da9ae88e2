"""One run of one cell: set-up, the measured window, the check, the
metrics.  ``run.py`` is the command; this module is what it and the
rehearsal test drive.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``configs/<name>.json``, ``traffic/<name>.json`` and
``metrics/<name>.py`` (a ``read(run)`` that returns a number, or None
where it finds nothing to read).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import gen, load, oracle
from .yardstick import meets

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DRAIN_S = 60.0            # answers are awaited this long past the close


def result_line(out: dict) -> str:
    """The run's last line of stdout."""
    return json.dumps(out)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(benchmark: dict, workload: str):
    """-> (workload entry, config, traffic, end-to-end metrics,
    per-layer metrics) for one cell of ``BENCHMARK.json``; metrics as
    (name, unit) pairs, those whose ``workloads`` leave the cell out
    left out."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in benchmark["configs"]}[w["config"]]
    cfg = read_json(os.path.join(ROOT, conf["file"]))
    traffic = read_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))

    def names(kind):
        return [(m["name"], m["unit"]) for m in benchmark[kind]
                if workload in m.get("workloads", [workload])]

    return w, cfg, traffic, names("end_to_end"), names("per_layer")


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What one run measured, handed to the metric readers."""
    cfg: dict
    traffic: dict
    seconds: float
    mbrs: np.ndarray                  # the run's objects (host copy)
    part_boxes: np.ndarray            # (T, 4) partition boxes
    part_valid: np.ndarray            # (T,) bool
    setup_s: float
    partition_s: float
    reqs: list                        # load.Req of the window
    t0: float                         # window start, perf_counter s
    frontend: dict                    # FrontendMetrics counters
    device_kind: str
    trace: dict | None = None         # xplane.load() of the window
    reduced: dict | None = None       # xplane.reduce() of the window
    batches: list | None = None       # (kind, qboxes, n_answers) traced
    _tiles: tuple | None = None

    def answered_in_window(self) -> int:
        t_end = self.t0 + self.seconds
        return sum(1 for r in self.reqs if 0 <= r.done <= t_end)

    def tile_objects(self):
        """(overlap (T,), first (T,)) object counts per partition."""
        if self._tiles is None:
            from .yardstick import tile_objects
            self._tiles = tile_objects(self.mbrs, self.part_boxes,
                                       self.part_valid)
        return self._tiles


class TracedServer:
    """Proxy over the server for the traced run: each batched call runs
    inside a ``bench.<kind>`` host span, waits for its result there, and
    its query boxes are recorded for the roofline."""

    def __init__(self, server, jax):
        self._server, self._jax = server, jax
        self.batches: list = []

    def __getattr__(self, name):
        return getattr(self._server, name)

    def _traced(self, kind, fn, qboxes, *args, **kw):
        with self._jax.profiler.TraceAnnotation("bench." + kind):
            out = self._jax.block_until_ready(fn(qboxes, *args, **kw))
        if kind != "knn":
            counts = np.asarray(out[0] if kind == "range_counts" else out[1])
            n_answers = (len(counts) if kind == "range_counts"
                         else int(np.minimum(counts, kw.get(
                             "max_hits", 1024)).sum()) + len(counts))
            self.batches.append((kind, np.asarray(qboxes), n_answers))
        return out

    def range_counts(self, qboxes, *a, **kw):
        return self._traced("range_counts", self._server.range_counts,
                            qboxes, *a, **kw)

    def range_ids(self, qboxes, *a, **kw):
        return self._traced("range_ids", self._server.range_ids, qboxes,
                            *a, **kw)

    def knn(self, pts, *a, **kw):
        return self._traced("knn", self._server.knn, pts, *a, **kw)


def build_server(jax, cfg: dict, mbrs, devices):
    """Partition and stage, as a user of the program does, serving over
    a mesh of ``devices`` where there is more than one ->
    (server, partition seconds)."""
    from jax.sharding import Mesh
    from repro.core.partition import api
    from repro.serve import ServeConfig, SpatialServer

    config = ServeConfig(**cfg.get("serve", {}))
    mesh = (None if len(devices) == 1
            else Mesh(np.asarray(devices), (config.axis,)))
    t = time.perf_counter()
    parts = api.partition(cfg["partitioner"], mbrs, cfg["payload"])
    jax.block_until_ready(parts)
    partition_s = time.perf_counter() - t
    server = SpatialServer(parts, mbrs, config, mesh=mesh,
                           method=cfg["partitioner"])
    return server, partition_s


def requests(cfg: dict, traffic: dict, seed: int, seconds: float,
             mbrs: np.ndarray, part_boxes: np.ndarray) -> list:
    """The run's requests (``load.requests_for``).  A closed loop's
    pool with a ``pool_seed`` is the same for every seed, drawn at the
    object centres of the deployment's ``pool_seed`` draw, and only its
    order comes from ``seed``: a batch job's lookups do not change
    from run to run, and the kNN frontier the pool converges to is set
    by its hardest point."""
    rng = np.random.default_rng(seed)
    if "pool_seed" not in traffic:
        centres = (mbrs[:, :2] + mbrs[:, 2:]) * 0.5
        return load.requests_for(rng, traffic, seconds, centres, part_boxes)
    pool = np.asarray(gen.dataset(cfg, traffic["pool_seed"]))
    reqs = load.requests_for(np.random.default_rng(traffic["pool_seed"]),
                             traffic, seconds,
                             (pool[:, :2] + pool[:, 2:]) * 0.5, part_boxes)
    return [reqs[i] for i in rng.permutation(len(reqs))]


def warm_up(server, traffic: dict, reqs: list, ladder) -> None:
    """Compile and run every batch shape the window can use, through
    the executor the frontend drives: each request class at each rung
    of the frontend's ladder.  Range classes first see their widest
    query, so the server's candidate width is already at its largest;
    kNN classes first run the whole request set, so its frontier width
    has converged, and each further ``max_cand`` runs the requests the
    one before it flagged."""
    from repro.serve.frontend import Batch, Request, execute_batch

    top = ladder[-1]

    def run(kind, params, payloads, width):
        rq = [Request(kind=kind, payload=p, params=params)
              for p in payloads[:width]]
        return execute_batch(server, Batch(kind, params, rq, width, 0.0))

    def rungs(kind, params, payloads):
        for w in ladder:
            run(kind, params, np.resize(payloads, (w,) + payloads.shape[1:]),
                w)

    probe = np.asarray(server.probe_boxes)
    for kind in ("range_counts", "range_ids"):
        boxes = [r.payload for r in reqs if r.kind == kind]
        if not boxes:
            continue
        boxes = np.stack(boxes)
        widest = boxes[int(np.argmax(meets(boxes, probe).sum(axis=1)))]
        params = () if kind == "range_counts" else (traffic["max_hits"],)
        rungs(kind, params, np.concatenate([widest[None], boxes]))
    todo = np.asarray([r.payload for r in reqs if r.kind == "knn"],
                      np.float32).reshape(-1, 2)
    for mc in traffic.get("max_cand", []) if len(todo) else []:
        params = (traffic["k"], mc)
        flagged = []
        for lo in range(0, len(todo), top):
            chunk = todo[lo:lo + top]
            width = next(w for w in ladder if w >= len(chunk))
            out = run("knn", params, chunk, width)
            flagged.extend(p for p, o in zip(chunk, out) if o[2])
        rungs("knn", params, todo)
        # the next max_cand sees what this one flagged (at least one
        # point, so that its shapes are warm all the same)
        todo = np.asarray(flagged or todo[:1], np.float32).reshape(-1, 2)


def settle() -> None:
    """End set-up as a long-running server process would: collect, then
    move every object set-up made (the imported program, its compiled
    functions, the staged mirrors, the request set) out of the cyclic
    collector's generations, so that a full collection in the window
    walks only what the window allocates."""
    gc.collect()
    gc.freeze()


class GcPauses:
    """The cyclic collector's pauses while registered: the longest, and
    how many were full (generation 2) collections."""

    def __init__(self):
        self.longest_s, self.full, self._t = 0.0, 0, 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.longest_s = max(self.longest_s, time.perf_counter() - self._t)
        self.full += info["generation"] == 2


def sample(rng, reqs: list, traffic: dict) -> dict:
    """A seeded sample of ``traffic["check"]`` requests of each kind
    -> {kind: [load.Req]}, in request order."""
    out = {}
    for kind in load.KINDS:
        mine = [r for r in reqs if r.kind == kind]
        if mine:
            pick = rng.choice(len(mine), min(traffic["check"], len(mine)),
                              replace=False)
            out[kind] = [mine[i] for i in np.sort(pick)]
    return out


def checked_answers(picked: dict, traffic: dict) -> dict:
    """The answered requests of a sample, in the shape
    ``oracle.compare`` takes."""
    out = {}
    for kind, reqs in picked.items():
        done = [r for r in reqs if r.done >= 0]
        if not done:
            continue
        q = np.stack([r.payload for r in done])
        if kind == "knn":
            out[kind] = (q, traffic["k"], [r.value[:2] for r in done])
        else:
            out[kind] = (q, [r.value for r in done])
    return out


def run_cell(jax, cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, metrics_wanted: list, t_start: float,
             devices, server_hook=None) -> dict:
    """One run -> the result line's dict (``correct``, ``attempted``,
    ``failed``, ``metrics``, ``device``, [``breakdown``], ``checks``).
    ``metrics_wanted``: (name, unit) of each metric to report.

    ``server_hook(server) -> server`` wraps the served object (the
    rehearsal test breaks the timed path with it)."""
    from repro.serve.frontend import FrontendConfig, ServeFrontend

    from . import xplane
    from .yardstick import CompileClock

    clock = CompileClock(jax)
    mbrs = gen.dataset(cfg, seed)
    mbrs_np = np.asarray(mbrs)
    server, partition_s = build_server(jax, cfg, mbrs, devices)
    part_boxes = np.asarray(server.parts.boxes)
    part_valid = np.asarray(server.parts.valid)
    reqs = requests(cfg, traffic, seed, seconds, mbrs_np,
                    part_boxes[part_valid])
    fe_config = FrontendConfig(**traffic.get("frontend", {}))
    warm_up(server, traffic, reqs, fe_config.ladder)
    served = server if server_hook is None else server_hook(server)
    traced = None
    if trace:
        traced = served = TracedServer(served, jax)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    compiles_before = clock.events
    settle()
    pauses = GcPauses()
    gc.callbacks.append(pauses)

    async def window():
        async with ServeFrontend(served, fe_config) as fe:
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            if trace:
                xplane.start(trace_dir)
            with jax.profiler.TraceAnnotation("bench.window"):
                if traffic["loop"] == "open":
                    late = await load.open_loop(fe, reqs, traffic, t0,
                                                DRAIN_S)
                    sent = reqs
                else:
                    late = 0.0
                    sent = await load.closed_loop(fe, reqs, traffic, t0,
                                                  seconds, DRAIN_S)
            snap = dict(batches=fe.metrics.batches,
                        batch_slots=fe.metrics.batch_slots,
                        batch_fill=fe.metrics.batch_fill,
                        rejected=fe.metrics.rejected)
        return t0, setup_s, late, sent, snap

    t0, setup_s, late, sent, snap = asyncio.run(window())
    gc.callbacks.remove(pauses)
    window_compiles = clock.events - compiles_before
    loaded = reduced = batches = None
    if trace:
        jax.profiler.stop_trace()
        loaded = xplane.load(xplane.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the measured window, not the drain after it
        lo, _ = xplane.window_of(loaded["spans"])
        reduced = xplane.reduce(loaded, (lo, lo + seconds * 1e9))
        batches = traced.batches
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    # the program's state goes before the reference runs
    del served, traced, server, mbrs
    gc.unfreeze()
    gc.collect()

    run = Run(cfg=cfg, traffic=traffic, seconds=seconds, mbrs=mbrs_np,
              part_boxes=part_boxes, part_valid=part_valid, setup_s=setup_s,
              partition_s=partition_s, reqs=sent, t0=t0, frontend=snap,
              device_kind=devices[0].device_kind, trace=loaded,
              reduced=reduced, batches=batches)
    # a request that never got its final answer (none came, or the
    # last max_cand still came back flagged) is unanswered; one the
    # frontend refused is failed but said so
    unanswered = sum(1 for r in sent
                     if r.done < 0 and r.outcome != "rejected")
    picked = sample(np.random.default_rng(seed + 1), sent, traffic)
    numbers = oracle.compare(mbrs_np, checked_answers(picked, traffic),
                             unanswered)
    metrics = {}
    for name, unit in metrics_wanted:
        value = reader(name)(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": oracle.verdict(numbers), "attempted": len(sent),
           "failed": sum(1 for r in sent if r.done < 0),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = oracle.checks_line(numbers)
    notes = dict(generator_late_ms=late * 1e3,
                 gc_longest_ms=pauses.longest_s * 1e3, gc_full=pauses.full,
                 window_compiles=window_compiles, compile_s=clock.secs,
                 cache_hits=clock.hits, rejected=snap["rejected"],
                 answered_in_window=run.answered_in_window())
    for k, v in notes.items():
        print(f"note {k}={v}", file=sys.stderr)
    for k, (v, lim) in out["checks"].items():
        print(f"check {k}={v} limit={lim}", file=sys.stderr, flush=True)
    return out
