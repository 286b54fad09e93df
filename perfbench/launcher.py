#!/usr/bin/env python3
"""The child of a benchmark run: takes the chip, builds the cluster, starts
the agent, and serves until the parent says quit.

The parent (`run.py`) is the load generator and the clock and never imports
JAX; this process holds the device. They talk over the agent's HTTP port and
over this process's stdin/stdout, one JSON object per line:

    -> {"cmd": "compiles"}     <- compilations and cache loads so far
    -> {"cmd": "snap"}         <- registry, compile and collector counters
    -> {"cmd": "trace_start"}  <- {"ok": true}
    -> {"cmd": "trace_stop"}   <- {"ok": true}
    -> {"cmd": "reduce"}       <- the trace reduced (`xplane.py`)
    -> {"cmd": "memory"}       <- `memory_stats()` of the fullest device
    -> {"cmd": "quit"}         <- {"ok": true}, then exit 0

No chip, no number: unless `jax.devices()[0].platform` is `tpu` this exits
non-zero before it says ready. `--rehearsal` is the CPU dress run, asked for
explicitly, at the tiny size the configuration's file names.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: tracing and lowering: paid also where the backend compile is skipped
TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")


def log(msg: str) -> None:
    print(f"launcher: {msg}", file=sys.stderr, flush=True)


def cache_entries(path: str) -> int:
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0


class Watch:
    """Compilations (and persistent-cache loads: the same JAX event covers
    both) and collector pauses of this process, counted and never steered."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.compiles = 0
        self.names = []
        self.trace_lower_s = 0.0
        self.gc_pause_s = 0.0
        self.gc_pause_max_s = 0.0
        self._gc_t0 = 0.0

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self.lock:
                self.compiles += 1
                self.names.append([str(kw.get("fun_name", "?")),
                                   round(float(duration), 4)])
                del self.names[:-64]
        elif event in TRACE_EVENTS:
            with self.lock:
                self.trace_lower_s += float(duration)

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0:
            pause = time.perf_counter() - self._gc_t0
            self.gc_pause_s += pause
            self.gc_pause_max_s = max(self.gc_pause_max_s, pause)
            self._gc_t0 = 0.0


def shapes_compiled() -> dict:
    """How many shapes each placement program holds compiled, from its own
    jit cache: a count that grows inside a window names the program whose
    shape escaped the warm-up."""
    from nomad_tpu.kernels import placement

    out = {}
    for name in ("place_table_chain", "place_table_wave",
                 "place_task_group_jit"):
        size = getattr(getattr(placement, name, None), "_cache_size", None)
        if callable(size):
            out[name] = int(size())
    return out


def load_cluster(server, cfg: dict, seed: int) -> dict:
    import adapter
    import cluster as cl

    t0 = time.time()
    recs = cl.make_nodes(cfg, seed)
    fillers = cl.make_fillers(cfg, seed, recs)
    nodes = [adapter.to_node(r) for r in recs]
    for node in nodes:
        server.node_register(node)
    t1 = time.time()
    jobs = []
    for k, jid in enumerate(cl.filler_job_ids(cfg, seed)):
        spec = cl.make_job(cfg, seed, -1 - k, "binpack", 8)
        spec["id"] = jid
        job = adapter.to_job(spec)
        server.state.upsert_job(job)
        jobs.append(job)
    for rec in fillers:
        server.state.upsert_alloc(adapter.to_filler_alloc(
            rec, nodes[rec["node"]], jobs[rec["job"]]))
    return {"nodes_s": round(t1 - t0, 3),
            "allocs_s": round(time.time() - t1, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path underneath "
                         "(`faults.py`)")
    args = ap.parse_args(argv)

    # stdout is the control pipe; whatever else prints goes to stderr
    pipe = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def say(obj) -> None:
        pipe.write(json.dumps(obj) + "\n")
        pipe.flush()

    with open(args.config) as f:
        cfg = json.load(f)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"  # stated, not fallen back to
        cfg.update(cfg["rehearsal"])
    try:
        from nomad_tpu.lib import backend
    except ImportError as e:
        log(f"the program is not here: {e}")
        return 3
    t_start = time.time()
    cache_dir = backend.setup_compile_cache()
    import jax

    # every program goes to the persistent cache, also the ones that
    # compile in under a second: a cell's second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_before = cache_entries(cache_dir)
    try:
        dev = backend.resolve()
    except RuntimeError as e:
        log(str(e))
        return 3
    if dev.platform != "tpu" and not args.rehearsal:
        log(f"needs a TPU, JAX reports platform={dev.platform}; a CPU "
            f"dress run is --rehearsal")
        return 3
    if args.fault:
        import faults

        faults.plant(args.fault)
        log(f"FAULT planted: {args.fault}")
    watch = Watch()
    jax.monitoring.register_event_duration_secs_listener(watch.on_duration)
    gc.callbacks.append(watch.on_gc)

    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.lib.metrics import default_registry

    agent = Agent(AgentConfig(server=True, client=False, http_port=0,
                              data_dir=None, heartbeat_ttl=3600.0))
    timings = load_cluster(agent.server, cfg, args.seed)
    t0 = time.time()
    agent.start()
    timings["agent_start_s"] = round(time.time() - t0, 3)
    timings["child_total_s"] = round(time.time() - t_start, 3)
    tc = agent.server.state.cluster
    if int(tc.n_cap) != int(cfg["row_bucket"]):
        log(f"the configuration states a row bucket of {cfg['row_bucket']}"
            f", the program's table has {tc.n_cap}")
        agent.shutdown()
        return 3
    log(f"platform={dev.platform} kind={dev.device_kind} devices={dev.count}"
        f" nodes={cfg['nodes']} allocs={cfg['allocs']} row_bucket="
        f"{tc.n_cap} cache={cache_dir} ({cache_before} entries) {timings}")
    say({"ready": True, "port": agent.http_addr[1],
         "host": agent.http_addr[0],
         "device": {"platform": dev.platform, "kind": dev.device_kind,
                    "count": dev.count},
         "row_bucket": int(tc.n_cap), "timings": timings,
         "cache_dir": cache_dir, "cache_entries": cache_before,
         "jax": jax.__version__})

    trace_dir = os.path.join(args.out, f"trace-{os.getpid()}")
    traced = [0.0, 0.0]  # tracing on, tracing off, on this process's clock
    rc = 0
    try:
        for line in sys.stdin:
            cmd = json.loads(line).get("cmd")
            if cmd == "compiles":
                say({"compiles": watch.compiles})
            elif cmd == "snap":
                with watch.lock:
                    say({"t": time.monotonic(),
                         "server": agent.server.metrics.snapshot(),
                         "process": default_registry().snapshot(),
                         "compiles": watch.compiles,
                         "compile_names": list(watch.names),
                         "gc_pause_s": watch.gc_pause_s,
                         "gc_pause_max_s": watch.gc_pause_max_s,
                         "trace_lower_s": watch.trace_lower_s,
                         "shapes_compiled": shapes_compiled(),
                         "cache_entries": cache_entries(cache_dir)})
            elif cmd == "trace_start":
                shutil.rmtree(trace_dir, ignore_errors=True)
                # the device planes are all that is read: no Python
                # tracer (it hooks every call of every thread), host
                # events at their coarsest
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                traced[0] = time.monotonic()
                say({"ok": True, "t": traced[0]})
            elif cmd == "trace_stop":
                traced[1] = time.monotonic()
                jax.profiler.stop_trace()
                say({"ok": True, "t": traced[1]})
            elif cmd == "reduce":
                import xplane

                try:
                    out = xplane.reduce_dir(trace_dir,
                                            traced[1] - traced[0])
                finally:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                say(out)
            elif cmd == "memory":
                stats = [d.memory_stats() or {} for d in jax.devices()]
                say({"peak_bytes_in_use": max(
                        (int(s.get("peak_bytes_in_use", 0)) for s in stats),
                        default=0),
                     "bytes_limit": max(
                        (int(s.get("bytes_limit", 0)) for s in stats),
                        default=0)})
            elif cmd == "quit":
                say({"ok": True})
                break
            else:
                say({"error": f"unknown command {cmd!r}"})
    except Exception as e:  # noqa: BLE001 — the parent reads the reason
        import traceback

        traceback.print_exc()
        say({"error": f"{type(e).__name__}: {e}"})
        rc = 1
    finally:
        agent.shutdown()
    return rc


if __name__ == "__main__":
    sys.exit(main())
