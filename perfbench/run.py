#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process is the load generator and the clock. It never imports JAX: the
chip belongs to its one child (`launcher.py`), which builds the cluster from
the seed, starts the agent and serves. Requests go to the agent's HTTP port
(`PUT /v1/jobs`); completions are PUSHED: one subscriber on
`/v1/event/stream?stream=1&topic=Eval` stamps each terminal `EvalUpdated`
on this process's clock as it arrives. No sleep stands between a completion
and its stamp, and nothing on a timed path polls.

A run: set-up (child start, cluster load, warm-up of every shape the cell's
traffic uses, then the cell's own traffic until no program has compiled or
loaded for `quiet_s`) -> the window of `--seconds` -> drain -> read every
job's allocations back -> the reference's replay (`check.py`) -> the run's
record on the line before the last, the contract's object on the last.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import http.client  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import check  # noqa: E402
import cluster as cl  # noqa: E402
import stats  # noqa: E402
from reference import bf16  # noqa: E402
import traffic as tf  # noqa: E402

TERMINAL = ("complete", "failed", "cancelled")
#: registry counters whose growth over the window goes into the run's record
RECORDED_COUNTERS = (
    "spec.launches", "spec.certified", "spec.rolled_back",
    "view.carry_adopts", "view.chain_adopts", "view.carry_rejects",
    "view.chain_rejects", "pipeline.dispatches", "pipeline.programs",
    "wave.dispatches", "wave.programs", "wave.slots", "wave.collisions",
    "plan_apply.partial", "plan_apply.applied",
    "plan_apply.rejected_devices", "sched.device_offers",
    "sched.device_offer_retries", "sched.offers", "sched.offers_skipped",
    "drain.footprint_estimates", "drain.footprint_hits")


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def plugin(kind: str, name: str):
    """`perfbench/<kind>/<name>.py`, found by name: a later PR adds a
    reader or an end-to-end metric as a file, editing none."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Req:
    __slots__ = ("k", "spec", "payload", "burst", "due", "released", "sent",
                 "acked", "done", "status", "eval_id", "order", "job_index",
                 "finished", "error")

    def __init__(self, k, spec, payload, burst, due=None, released=None):
        self.k = k
        self.spec = spec
        self.payload = payload
        self.burst = burst      # a warm-up burst: releases no next job
        self.due = due          # open loop: the instant it is due
        self.released = released  # closed loop: when its slot came free
        self.sent = self.acked = self.done = None
        self.status = self.eval_id = self.error = None
        # the order the broker saw: the index of the eval's first event
        # (written, then enqueued, by one handler thread). The job's own
        # index is no order: two handlers can write job A, job B and then
        # enqueue eval B before eval A. It stands in where events were lost.
        # `finished`: the index of its terminal event (`check.serial_order`)
        self.order = self.job_index = self.finished = None


class Payloads:
    """`PUT /v1/jobs` bodies: the program's own wire form of each kind,
    made once through its codec and cut at the id and the asks; a job's
    body is the pieces joined around its own values (joined, not replaced
    one after the other: an id may hold the digits of a placeholder)."""

    SLOTS = {b"svc-0123456789ab": "id", b"987651": "cpu", b"987652": "memory"}

    def __init__(self, cfg: dict, count: int) -> None:
        import adapter

        self.templates = {}
        cut = re.compile(b"(" + b"|".join(self.SLOTS) + b")")
        for kind in cfg["mix"]:
            spec = cl.make_job(cfg, 0, 0, kind, count)
            spec.update({v: (k.decode() if v == "id" else int(k))
                         for k, v in self.SLOTS.items()})
            pieces = [self.SLOTS.get(p, p)
                      for p in cut.split(adapter.job_payload(spec))]
            slots = sorted(p for p in pieces if isinstance(p, str))
            if slots != ["cpu", "id", "id", "memory"]:
                raise RuntimeError(f"payload template of {kind}: {slots}")
            self.templates[kind] = pieces

    def of(self, spec: dict) -> bytes:
        return b"".join(p if isinstance(p, bytes) else str(spec[p]).encode()
                        for p in self.templates[spec["kind"]])


class Generator:
    """Senders, the subscriber and the book of requests."""

    def __init__(self, cfg, traffic, seed, host, port) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.host, self.port = host, port
        self.count = int(traffic["count"])
        self.payloads = Payloads(cfg, self.count)
        self.lock = threading.Lock()
        self.reqs = []
        self.by_job = {}
        self.k = 0          # jobs made
        self.mix_pos = 0    # position in the seeded sequence of kinds
        self.kinds = []
        self.sendq: "queue.SimpleQueue" = queue.SimpleQueue()
        self.done_cv = threading.Condition()
        self.closed_running = False
        self.epoch = 0      # closed loop: slots freed before a restart die
        self.gate = threading.Lock()
        self.feeding = False
        self.observe_lost = 0
        self.stream_error = None
        self.stream_up = threading.Event()
        self.threads = [threading.Thread(target=self._sender, daemon=True,
                                         name=f"sender-{i}")
                        for i in range(int(traffic["senders"]))]
        self.sub = threading.Thread(target=self._subscribe, daemon=True,
                                    name="subscriber")

    def start(self) -> None:
        self.sub.start()
        if not self.stream_up.wait(30.0):
            raise RuntimeError(f"event stream: {self.stream_error}")
        for t in self.threads:
            t.start()

    # ---- making requests ----

    def new_req(self, kind=None, due=None, released=None,
                burst=False) -> Req:
        with self.lock:
            if kind is None:
                if self.mix_pos >= len(self.kinds):
                    self.kinds = cl.kinds_sequence(
                        self.cfg, self.seed, len(self.kinds) + 4096)
                kind = self.kinds[self.mix_pos]
                self.mix_pos += 1
            k = self.k
            self.k += 1
        spec = cl.make_job(self.cfg, self.seed, k, kind, self.count)
        req = Req(k, spec, self.payloads.of(spec), burst, due, released)
        with self.lock:
            self.reqs.append(req)
            self.by_job[spec["id"]] = req
        return req

    # ---- senders ----

    def _sender(self) -> None:
        conn = None
        while True:
            item = self.sendq.get()
            if item is None:
                break
            if isinstance(item, tuple):  # a free slot, stamped when freed
                epoch, freed = item
                with self.gate:  # made before the loop stops, or not at all
                    if not self.closed_running or epoch != self.epoch:
                        continue
                    req = self.new_req(released=freed)
            else:
                req = item
            if req.due is not None:
                wait = req.due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)  # until the request is DUE, no more
            for attempt in (0, 1):
                try:
                    if conn is None:
                        conn = http.client.HTTPConnection(
                            self.host, self.port, timeout=120.0)
                    t0 = time.monotonic()
                    if attempt == 0:
                        req.sent = t0
                    conn.request("PUT", "/v1/jobs", body=req.payload,
                                 headers={"Content-Type":
                                          "application/json"})
                    res = conn.getresponse()
                    data = res.read()
                    req.acked = time.monotonic()
                    if res.status >= 400:
                        raise RuntimeError(f"HTTP {res.status}: "
                                           f"{data[:200]!r}")
                    out = json.loads(data)
                    req.eval_id = out.get("eval_id")
                    req.job_index = out.get("job_modify_index")
                    break
                except (OSError, http.client.HTTPException) as e:
                    if conn is not None:
                        conn.close()
                    conn = None
                    if attempt == 1:
                        self._fail(req, f"{type(e).__name__}: {e}")
                except RuntimeError as e:
                    self._fail(req, str(e))
                    break
        if conn is not None:
            conn.close()

    def _fail(self, req: Req, why: str) -> None:
        req.error = why
        self._finish(req, "not-accepted", time.monotonic())

    def _finish(self, req: Req, status: str, now: float) -> None:
        with self.done_cv:
            if req.done is not None:
                return
            req.done, req.status = now, status
            self.done_cv.notify_all()
        if self.closed_running and not req.burst:
            self.sendq.put((self.epoch, now))

    # ---- completions, pushed ----

    def _subscribe(self) -> None:
        from nomad_tpu.api.client import NomadClient

        api = NomadClient(self.host, self.port, timeout=3600.0)
        try:
            stream = api.event_stream(topics=["Eval"], heartbeat=1.0,
                                      yield_heartbeats=True)
            for batch in stream:
                now = time.monotonic()
                self.stream_up.set()
                for e in batch.get("events") or ():
                    if e.get("type") == "lost-gap":
                        threading.Thread(target=self._recover,
                                         daemon=True).start()
                        continue
                    p = e.get("payload") or {}
                    req = self.by_job.get(p.get("job_id"))
                    if req is None:
                        continue
                    if req.order is None:
                        # the eval's first event: the index at which it
                        # was written, just before it was enqueued
                        req.order = p.get("modify_index") or e.get("index")
                    if p.get("status") in TERMINAL and req.done is None:
                        req.finished = p.get("modify_index") or e.get("index")
                        self._finish(req, p["status"], now)
        except Exception as e:  # noqa: BLE001 — reported by the main thread
            self.stream_error = f"{type(e).__name__}: {e}"
            self.stream_up.set()

    def _recover(self) -> None:
        """A `lost-gap` marker: events were dropped. Read the evals that
        are still open once; they count in `observe_lost`."""
        with self.lock:
            open_reqs = [r for r in self.reqs
                         if r.done is None and r.eval_id]
        conn = http.client.HTTPConnection(self.host, self.port, timeout=60)
        try:
            for r in open_reqs:
                conn.request("GET", f"/v1/evaluation/{r.eval_id}")
                ev = json.loads(conn.getresponse().read())
                if ev.get("status") in TERMINAL and r.done is None:
                    self.observe_lost += 1
                    self._finish(r, ev["status"], time.monotonic())
        finally:
            conn.close()

    # ---- phases ----

    def burst(self, n: int, kinds=None, timeout: float = 900.0) -> list:
        """`n` jobs at once; returns when all are answered."""
        reqs = [self.new_req(kind=kinds[i % len(kinds)] if kinds else None,
                             burst=True) for i in range(n)]
        for r in reqs:
            self.sendq.put(r)
        self.wait(reqs, timeout)
        return reqs

    def wait(self, reqs, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        with self.done_cv:
            while any(r.done is None for r in reqs):
                left = deadline - time.monotonic()
                if left <= 0 or self.stream_error:
                    return False
                self.done_cv.wait(min(left, 1.0))
        return True

    def start_steady(self) -> None:
        if self.traffic["loop"] == "closed":
            self.epoch += 1
            self.closed_running = True
            now = time.monotonic()
            for _ in range(int(self.traffic["outstanding"])):
                self.sendq.put((self.epoch, now))
        else:
            self.feeding = True
            self.feeder = threading.Thread(target=self._feed, daemon=True,
                                           name="feeder")
            self.feeder.start()

    def _feed(self) -> None:
        base = time.monotonic() + 0.2
        for off in tf.arrivals(float(self.traffic["rate_per_s"]),
                               self.seed):
            due = base + off
            lead = due - 0.05 - time.monotonic()
            if lead > 0:
                time.sleep(lead)
            if not self.feeding:
                return
            self.sendq.put(self.new_req(due=due))

    def stop_steady(self) -> None:
        with self.gate:
            self.closed_running = False
        self.feeding = False

    def shutdown(self) -> None:
        for _ in self.threads:
            self.sendq.put(None)
        for t in self.threads:
            t.join(10.0)


class Child:
    def __init__(self, argv) -> None:
        self.p = subprocess.Popen(argv, stdin=subprocess.PIPE,
                                  stdout=subprocess.PIPE, text=True,
                                  bufsize=1)

    def read(self) -> dict:
        line = self.p.stdout.readline()
        if not line:
            raise RuntimeError("the launcher has gone")
        out = json.loads(line)
        if "error" in out:
            raise RuntimeError(f"launcher: {out['error']}")
        return out

    def ask(self, cmd: str) -> dict:
        self.p.stdin.write(json.dumps({"cmd": cmd}) + "\n")
        self.p.stdin.flush()
        return self.read()

    def stop(self) -> None:
        if self.p.poll() is None:
            try:
                self.ask("quit")
            except (RuntimeError, OSError, ValueError):
                pass
            try:
                self.p.wait(30.0)
            except subprocess.TimeoutExpired:
                self.p.kill()
                self.p.wait()


def flat(snap: dict) -> dict:
    """The server's and the process's registries as one (their names are
    disjoint), beside the launcher's own counters."""
    out = {"counters": {}, "gauges": {}, "histograms": {}}
    for scope in ("process", "server"):
        for kind in out:
            out[kind].update(snap[scope].get(kind) or {})
    for k in ("t", "compiles", "compile_names", "gc_pause_s",
              "gc_pause_max_s", "trace_lower_s", "shapes_compiled",
              "cache_entries"):
        out[k] = snap[k]
    return out


def counters_between(a: dict, b: dict) -> dict:
    return {"counters": {k: v - a["counters"].get(k, 0.0)
                         for k, v in b["counters"].items()}}


def read_back(host, port, reqs, threads: int = 4) -> None:
    """Every eval's allocations over HTTP, outside any timing: those the
    store runs, and those a later allocation evicted (`evicted`), each
    with its id, the victims it evicted and its commit index."""
    todo: "queue.SimpleQueue" = queue.SimpleQueue()
    for r in reqs:
        todo.put(r)

    def work() -> None:
        conn = http.client.HTTPConnection(host, port, timeout=300.0)
        try:
            while True:
                try:
                    r = todo.get_nowait()
                except queue.Empty:
                    return
                r.spec["_allocs"] = allocs = []
                if not r.eval_id:
                    continue  # never accepted: nothing to read
                # the eval's own allocations, straight from the live
                # store: `/v1/job/<id>/allocations` snapshots the whole
                # store per GET, ~50 ms at 140,000 allocations
                conn.request("GET",
                             f"/v1/evaluation/{r.eval_id}/allocations")
                res = conn.getresponse()
                data = json.loads(res.read())
                if res.status >= 400:
                    continue
                for a in data or ():
                    rec = check.alloc_record(a)
                    if rec is not None:
                        allocs.append(rec)
        finally:
            conn.close()

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()


def warm_up(gen: Generator, child: Child, cfg: dict, wu: dict) -> dict:
    """Part of the cell, counted as set-up: every kind of the mix once (the
    program table's floor dims reach their last value), the bursts (every
    program-axis bucket the drain can deliver), then the cell's own traffic
    until `quiet_s` pass with no compilation and no cache load."""
    t_start = time.monotonic()
    for kind in cfg["mix"]:
        gen.burst(1, [kind])
    for b in wu["bursts"]:
        gen.burst(int(b["n"]), b.get("kinds"))
    t_bursts = time.monotonic()
    gen.start_steady()
    seen = child.ask("compiles")["compiles"]
    quiet_from = time.monotonic()
    while True:
        time.sleep(0.5)
        now = time.monotonic()
        n = child.ask("compiles")["compiles"]
        if n != seen:
            seen, quiet_from = n, now
        if gen.stream_error:
            raise RuntimeError(f"event stream: {gen.stream_error}")
        if now - quiet_from >= float(wu["quiet_s"]):
            break
        if now - t_start >= float(wu["max_s"]):
            log(f"warm-up: still compiling after {wu['max_s']} s")
            break
    return {"warmup_bursts_s": t_bursts - t_start,
            "warmup_steady_s": time.monotonic() - t_bursts}


def sent_so_far(gen: Generator, t1=None) -> list:
    """Whatever was sent or is on its way out of a sender: every job of a
    closed loop that was made, and whatever was due before `t1`."""
    with gen.lock:
        reqs = list(gen.reqs)
    return [r for r in reqs if r.due is None or r.sent is not None
            or r.done is not None or (t1 is not None and r.due < t1)]


def window(gen: Generator, child: Child, seconds: float, trace_span: float,
           timeout_s: float, drained: bool) -> dict:
    """Open the window on the running traffic, trace its middle if asked,
    close it, and wait for what it left in flight.

    A `drained` window (`"window": "drained"` in the traffic file: a closed
    loop whose jobs are answered in a few lumps a window) holds whole jobs
    only. The warm-up's traffic is stopped and waited for (set-up); the
    loop starts anew at the opening and sends for `seconds`; then nothing
    more is sent, all that was sent is waited for, and the clock is read
    after that wait: all of that work over all of that time. A window cut
    at a fixed instant counts one lump more or less by where the cut falls
    between two lumps."""
    if drained:
        gen.stop_steady()
        if not gen.wait(sent_so_far(gen), timeout_s):
            raise RuntimeError("the warm-up's traffic was never answered")
    start = flat(child.ask("snap"))
    t0 = time.monotonic()
    t1 = t0 + seconds
    if drained:
        gen.start_steady()
    # the window closes on a thread of its own: stopping a profile can hold
    # this one for tens of seconds past t1 (c1m-5k.flood), and a loop left
    # running meanwhile sends, and has read back, twice the window's jobs
    closed = {}

    def close() -> None:
        try:
            gen.stop_steady()
            closed["answered_all"] = gen.wait(sent_so_far(gen, t1),
                                              timeout_s)
        except BaseException as e:  # raised again where the run can end
            closed["error"] = e
        closed["t"] = time.monotonic()

    closer = threading.Timer(max(0.0, t1 - time.monotonic()), close)
    closer.daemon = True
    closer.start()
    traced = None
    if trace_span:
        span = min(trace_span, seconds / 2.0)
        time.sleep(max(0.0, (seconds - span) / 2.0))
        child.ask("trace_start")
        s_a = flat(child.ask("snap"))
        time.sleep(span)
        s_b = flat(child.ask("snap"))
        child.ask("trace_stop")
        traced = counters_between(s_a, s_b)
    time.sleep(max(0.0, t1 - time.monotonic()))
    if not drained:
        end = flat(child.ask("snap"))
    closer.join()
    if "error" in closed:
        raise closed["error"]
    t_close = closed["t"] if drained else t1
    if drained:
        end = flat(child.ask("snap"))
    w = {"start": start, "end": end, "t0": t0, "t1": t1, "traced": traced,
         "t_close": t_close, "drain_s": closed["t"] - t1,
         "answered_all": closed["answered_all"],
         "memory": child.ask("memory"),
         "trace": child.ask("reduce") if trace_span else None}
    gen.shutdown()
    return w


def metrics_of(bench: dict, workload: str, trace) -> list:
    """The entries of BENCHMARK.json a run of the cell reports: its
    per-layer metrics in a traced run, its end-to-end metrics otherwise.
    The entry's `workloads` list alone says where a metric is read (an
    entry without one is read in every cell), so a later PR gives a new
    cell its metrics by adding the cell's name to lists of BENCHMARK.json:
    `metrics/<name>.json` says how a metric is read and names no cell."""
    return [m for m in bench["per_layer" if trace else "end_to_end"]
            if workload in m.get("workloads", [workload])]


def judge(plain, reqs, asked, workload: str, seed: int, control: bool):
    """The reference's replay of the whole run, a sample of the window's
    answered evals compared (`check.py`)."""
    spec = load_json(HERE, "limits", f"{workload}.json")
    ordered = check.serial_order(
        [{"spec": r.spec, "allocs": r.spec["_allocs"],
          "enqueued": r.order or r.job_index, "finished": r.finished}
         for r in reqs if (r.order or r.job_index)])
    answered = [r for r in asked if r.status in TERMINAL]
    longest = max(answered, key=lambda r: (len(r.spec["_allocs"]), r.k),
                  default=None)
    sample = check.draw_sample([r.spec["id"] for r in answered],
                               longest.spec["id"] if longest else None,
                               int(spec["sample_evals"]), seed)
    numbers = check.replay(plain, ordered, sample,
                           rnd_control=bf16 if control else None)
    numbers["unanswered"] = sum(1 for r in asked if r.done is None)
    if control:
        # the control's numbers through the same comparison, with the
        # cell's own limits: it has to come out as not correct
        held = check.verdict(numbers["control"],
                             {k: v for k, v in spec["limits"].items()
                              if k in numbers["control"]})
        numbers["control"] = {
            "correct": all(c["ok"] for c in held.values()), "checks": held}
    return numbers, check.verdict(numbers, spec["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "perfbench_out"))
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dress run at a tiny size: counts and "
                         "`correct`, no device metric; never implied")
    ap.add_argument("--control", action="store_true",
                    help="also read the control: the reference in "
                         "bfloat16 in the program's place")
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path (launcher)")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    if not os.path.isdir(os.path.join(ROOT, "nomad_tpu")):
        log("the program is not here (no nomad_tpu/ beside perfbench/)")
        return 3
    cfg = load_json(ROOT, conf["file"])
    traffic = tf.load(cell["traffic"])
    if args.rehearsal:
        cfg.update(cfg["rehearsal"])
        traffic.update(traffic.get("rehearsal") or {})
    os.makedirs(args.out, exist_ok=True)

    argv_child = [sys.executable, os.path.join(HERE, "launcher.py"),
                  "--config", os.path.join(ROOT, conf["file"]),
                  "--seed", str(args.seed), "--out", args.out]
    if args.rehearsal:
        argv_child.append("--rehearsal")
    if args.fault:
        argv_child += ["--fault", args.fault]
    child = Child(argv_child)
    gen = None
    try:
        # while the child loads the cluster, make this side's copy of it
        plain = cl.Cluster(cfg, args.seed)
        try:
            ready = child.read()
        except RuntimeError:
            rc = child.p.wait()
            log(f"the launcher ended before it was ready (exit {rc}): no "
                f"accelerator, or fewer chips than the cell asks for")
            return rc or 3
        device = ready["device"]
        if device["count"] < int(cell["chips"]):
            log(f"the cell asks for {cell['chips']} chips, JAX has "
                f"{device['count']}")
            return 3
        gen = Generator(cfg, traffic, args.seed, ready["host"],
                        ready["port"])
        gen.start()
        # this process's own collector: the cluster's copy (100,000 plain
        # records) is set aside, so that no collection of the clock's
        # process walks it while requests are stamped
        gc.collect()
        gc.freeze()
        setup = warm_up(gen, child, cfg, traffic["warmup"])
        w = window(gen, child, args.seconds,
                   float(traffic.get("trace_span_s", 4.0))
                   if args.trace else 0.0, float(traffic["timeout_s"]),
                   traffic.get("window", "running") == "drained")
        # t1: the last instant a request was sent or due; t_close: the end
        # of the time the rates are taken over (later in a drained window)
        t0, t1, start, end = w["t0"], w["t1"], w["start"], w["end"]
        t_close = w["t_close"]
        setup_s = t0 - T_PROCESS

        # ---- what the window was asked, and what it answered ----
        with gen.lock:
            reqs = [r for r in gen.reqs if r.sent is not None]
        if traffic["loop"] == "open":
            asked = [r for r in reqs if r.due is not None
                     and t0 <= r.due < t1]
        else:
            asked = [r for r in reqs if t0 <= r.sent < t1]
        t_rb = time.monotonic()
        read_back(ready["host"], ready["port"], reqs)
        readback_s = time.monotonic() - t_rb
        for r in reqs:
            # a system job's count is its node set, which the replay judges
            r.spec["_ok"] = r.status == "complete" and (
                cl.job_type(r.spec) == "system"
                or len(r.spec["_allocs"]) == r.spec["count"])
        failed = [r for r in asked if not r.spec["_ok"]]
        t_check = time.monotonic()
        numbers, checks = judge(plain, reqs, asked, args.workload,
                                args.seed, args.control)
        check_s = time.monotonic() - t_check

        # ---- metrics ----
        timeout_ms = 1e3 * float(traffic["timeout_s"])
        run = {
            "t0": t0, "t1": t_close, "setup_s": setup_s, "reqs": reqs,
            # from the instant a request was DUE (open loop) or sent
            "latency_ms": [
                1e3 * (r.done - (r.due if r.due is not None else r.sent))
                if r.spec["_ok"] else timeout_ms for r in asked],
        }
        parent = {
            "generator_late_ms": stats.median(
                [1e3 * (r.sent - (r.due if r.due is not None
                                  else r.released)) for r in asked
                 if r.due is not None or r.released is not None]),
            "http_submit_ms": stats.median(
                [1e3 * (r.acked - r.sent) for r in asked
                 if r.acked is not None]),
        }
        trace = w["trace"]
        traced_ok = bool(trace and not trace.get("error")
                         and trace["devices"] and trace["window_s"] > 0)
        metrics = {}
        notes = {}
        # counts and `correct` alone in a rehearsal: a CPU gives no metric
        wanted = ([] if args.rehearsal
                  else metrics_of(bench, args.workload, args.trace))
        if wanted and args.trace:
            ctx = {"start": start, "end": end, "traced": w["traced"],
                   "trace": trace if traced_ok else None,
                   "memory": w["memory"], "parent": parent,
                   # the window's own length: `end` is read when a
                   # profile's stop lets it, after a server gone idle
                   "window_s": t_close - t0, "config": cfg,
                   "traffic": traffic, "device": device,
                   "row_bucket": ready["row_bucket"], "notes": notes}
        for m in wanted:
            if args.trace:
                spec = load_json(HERE, "metrics", f"{m['name']}.json")
                v = plugin("readers", spec["reader"]).read(spec, ctx)
            else:
                v = plugin("end_to_end", m["name"]).compute(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

        dev = dict(device,
                   memory_peak_bytes=w["memory"]["peak_bytes_in_use"])
        if traced_ok:
            dev["busy_s"] = trace["busy_s"]
            dev["window_s"] = trace["window_s"]
        by_kind = {}
        for r in asked:
            by_kind[r.spec["kind"]] = by_kind.get(r.spec["kind"], 0) + 1
        n_compiled = end["compiles"] - start["compiles"]
        stamps = sorted(r.done for r in reqs
                        if r.done is not None and t0 <= r.done < t_close)
        record = {
            "workload": args.workload, "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace,
            "rehearsal": bool(args.rehearsal),
            "nodes": cfg["nodes"], "allocs": cfg["allocs"],
            "row_bucket": ready["row_bucket"], "jax": ready["jax"],
            "loop": traffic["loop"], "count": traffic["count"],
            "attempted": len(asked), "failed": len(failed),
            "by_kind": by_kind, "jobs_of_the_run": len(reqs),
            "completed_in_window": sum(
                1 for r in reqs if r.spec["_ok"] and t0 <= r.done < t_close),
            "jobs_in_window": sum(1 for r in reqs if r.sent >= t0),
            "window": traffic.get("window", "running"),
            "window_s": t_close - t0,
            "observe_lost": gen.observe_lost,
            "not_accepted": [r.error for r in asked if r.error][:3],
            "generator_late_ms": parent["generator_late_ms"],
            "http_submit_ms": parent["http_submit_ms"],
            "latency_ms": dict(
                {f"p{q}": stats.percentile(run["latency_ms"], q)
                 for q in (50, 95, 99, 100)}, n=len(run["latency_ms"])),
            "completion_gap_max_ms": max(
                (1e3 * (b - a) for a, b in zip(stamps, stamps[1:])),
                default=None),
            # every job of the run on the window's clock (s from its
            # opening): sent, answered; the lumps of a closed loop show here
            "stamps": [[round(r.sent - t0, 3),
                        None if r.done is None else round(r.done - t0, 3)]
                       for r in reqs],
            "compiles_in_window": n_compiled,
            "compiled_in_window": (end["compile_names"][-n_compiled:]
                                   if n_compiled else []),
            "compiles_in_setup": start["compiles"],
            "shapes_compiled": [start["shapes_compiled"],
                                end["shapes_compiled"]],
            "cache_entries": [ready["cache_entries"],
                              end["cache_entries"]],
            "gc_pause_s_in_window": end["gc_pause_s"] - start["gc_pause_s"],
            "gc_pause_max_s": end["gc_pause_max_s"],
            "trace_lower_s_in_window": end["trace_lower_s"]
            - start["trace_lower_s"],
            "drain_window_ms": [start["gauges"].get("drain.window_ms"),
                                end["gauges"].get("drain.window_ms")],
            "counters_in_window": {
                c: end["counters"].get(c, 0) - start["counters"].get(c, 0)
                for c in RECORDED_COUNTERS},
            "fill_at_end": numbers["fill"],
            "gpus_in_use": numbers["gpus_in_use"],
            "system": {k: numbers[k] for k in (
                "system_placed", "system_refused", "evictions",
                "system_interleaved")},
            "setup": dict(setup, total_s=setup_s, child=ready["timings"]),
            "after_window": {"drain_s": w["drain_s"],
                             "answered_all": w["answered_all"],
                             "readback_s": readback_s, "check_s": check_s,
                             "compared": numbers["compared"]},
            "worst": numbers["worst"], "notes": notes,
            "control": numbers.get("control"),
            "trace_programs": (trace or {}).get("programs"),
        }
        if args.rehearsal:  # a CPU run gives counts, never a time
            for k in ("generator_late_ms", "http_submit_ms", "latency_ms",
                      "setup", "gc_pause_s_in_window", "gc_pause_max_s",
                      "completion_gap_max_ms", "trace_lower_s_in_window",
                      "stamps", "window_s"):
                record[k] = None
        with open(os.path.join(
                args.out, f"{args.workload}.seed{args.seed}."
                          f"trace{args.trace}.json"), "w") as f:
            json.dump(record, f)
        result = {"correct": all(c["ok"] for c in checks.values()),
                  "attempted": len(asked), "failed": len(failed),
                  "metrics": metrics, "device": dev}
        if args.rehearsal:
            result["rehearsal"] = True
        if traced_ok:
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                            for k, c in checks.items()}
    finally:
        if gen is not None:
            gen.stop_steady()
        child.stop()
    print(json.dumps(record), flush=True)
    for who, held in (("control", (record["control"] or {}).get("checks")),
                      ("check", checks)):
        for name, c in (held or {}).items():
            log(f"{who} {name}: {c['value']!r} (limit {c['limit']!r}) "
                f"{'ok' if c['ok'] else 'NOT WITHIN ITS LIMIT'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
