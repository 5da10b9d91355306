"""One run of one cell: set up, warm up, measure an open-loop window
through ``IndexService``, check every answer against the reference, print
the result line.

    python benchmarks/chip/run.py --workload email-c --seed 7 --seconds 10 --trace 0

Set-up is loading (the corpus and an index snapshot from the cache under
``benchmarks/chip/.cache/``, built and saved by the first run in a
checkout), starting the service, and warm-up: every program shape the
cell's traffic can meet, then a replay of the cell's own traffic at its
rate.  The window then submits gets on their open-loop schedule, one
future per op, and times each from when it was due to when the client
holds its answer.  After the window the reference answers every op of
every flush the service made, and each answer is compared with it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import resource
import shutil
import sys
import threading
import time
from typing import Callable, Optional

import numpy as np

from . import corpus as corpus_mod
from . import trace as trace_mod
from . import ycsb
from .compiles import CompileCounter
from .oracle import Oracle, get_key
from .recorder import RecordingIndex
from .spec import BENCH_DIR, ROOT, Cell, load_cell, tree_hash

CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
PEAKS_FILE = os.path.join(BENCH_DIR, "peaks.json")
GRACE_S = 60.0            # how long past the window an answer may come
TRACE_WINDOW_S = 3.0      # the longest window a --trace 1 run measures


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------

def check_devices(chips: int):
    """The devices a run uses; exits non-zero, printing no result, unless
    JAX sees at least ``chips`` TPUs of a kind the peak table knows."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {devs[0].platform!r}; "
                         "this benchmark runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    with open(PEAKS_FILE) as f:
        peaks = json.load(f)["devices"]
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"device kind {devs[0].device_kind!r} is not in "
                         f"{PEAKS_FILE}")
    return devs[:chips]


def enable_compile_cache(cache_dir: str = CACHE_DIR) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(cache_dir, "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# ---------------------------------------------------------------------------
# set-up: corpus and index, from the cache when it has them
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Loaded:
    corpus: corpus_mod.Corpus
    index: object           # repro.index.StringIndex
    times: dict             # seconds per set-up step


def _atomic(path: str, write: Callable[[str], None]) -> None:
    tmp = path + ".part"
    write(tmp)
    os.replace(tmp, path)


def load_index(cell: Cell, cache_dir: str = CACHE_DIR) -> Loaded:
    from repro.index import IndexConfig, StringIndex
    from repro.serve.service import IndexService

    conf = cell.config
    times = {}
    where = os.path.join(cache_dir, cell.config_name)
    os.makedirs(where, exist_ok=True)
    corpus_path = os.path.join(where, f"corpus-{cell.config_hash}.npz")
    t = time.perf_counter()
    if os.path.exists(corpus_path):
        corpus = corpus_mod.load(corpus_path)
        times["corpus_load_s"] = time.perf_counter() - t
    else:
        corpus = corpus_mod.build(conf["dataset"], conf["recordcount"],
                                  conf["corpus_seed"])
        times["corpus_generate_s"] = time.perf_counter() - t
        _atomic(corpus_path, lambda p: corpus_mod.save(corpus, p))
    icfg = IndexConfig(**conf["index"])
    snap = os.path.join(
        where, f"index-{cell.config_hash}-{tree_hash(os.path.join(ROOT, 'src'))}.npz")
    t = time.perf_counter()
    if os.path.exists(snap):
        index = StringIndex.load(snap, icfg)
        times["snapshot_load_s"] = time.perf_counter() - t
    else:
        keys = [IndexService.encode_key(conf["tenant"], k)
                for k in corpus.key_list()]
        built = StringIndex.bulk_load(keys, corpus.values, icfg)
        times["bulk_build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        _atomic(snap, built.save)
        times["snapshot_save_s"] = time.perf_counter() - t
        # every run serves the index as a restart loads it: the built
        # one's device arrays differ from the loaded one's, so the first
        # run would serve another index than the runs after it
        del built
        gc.collect()
        t = time.perf_counter()
        index = StringIndex.load(snap, icfg)
        times["snapshot_load_s"] = time.perf_counter() - t
    return Loaded(corpus, index, times)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

def build_requests(stream: ycsb.OpStream, corpus: corpus_mod.Corpus) -> list:
    from repro.index import GetRequest

    return [GetRequest(corpus.key(i)) for i in stream.item.tolist()]


# ---------------------------------------------------------------------------
# the open-loop window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    reqs: list
    due: np.ndarray           # seconds after t0
    t0: float = 0.0
    submitted: Optional[np.ndarray] = None   # perf_counter at submission
    done: Optional[np.ndarray] = None        # perf_counter when answered
    futures: Optional[list] = None

    def answers(self) -> list:
        out = [f.result(0) if f.done() else None for f in self.futures]
        return out + [None] * (len(self.reqs) - len(out))


def run_window(svc, tenant: str, reqs: list, due: np.ndarray,
               trace_span: Optional[str] = None) -> Window:
    """Submit ``reqs`` on their schedule from this thread; a second thread
    stamps each answer as the client receives it.  Returns once every op
    is answered or ``GRACE_S`` past the last due time."""
    import jax

    n = len(reqs)
    w = Window(reqs, due, submitted=np.zeros(n), done=np.full(n, np.nan),
               futures=[])
    futs = w.futures

    def collect():
        i = 0
        while i < n:
            if i >= len(futs):
                time.sleep(0.0002)
                continue
            try:
                futs[i].result(timeout=max(
                    w.t0 + due[-1] + GRACE_S - time.perf_counter(), 0.001))
            except TimeoutError:
                return
            now = time.perf_counter()
            w.done[i] = now
            i += 1
            while i < len(futs) and futs[i].done():
                w.done[i] = now
                i += 1

    collector = threading.Thread(target=collect, name="bench-collector",
                                 daemon=True)
    span = jax.profiler.TraceAnnotation(trace_span) if trace_span else None
    if span is not None:
        span.__enter__()
    w.t0 = t0 = time.perf_counter()
    collector.start()
    i = 0
    try:
        while i < n:
            now = time.perf_counter() - t0
            if due[i] > now:
                time.sleep(min(due[i] - now, 0.005))
                continue
            j = int(np.searchsorted(due, now, side="right"))
            with jax.profiler.TraceAnnotation("bench.generator.submit"):
                futs.extend(svc.submit_many(reqs[i:j], tenant))
            w.submitted[i:j] = time.perf_counter()
            i = j
        # the window closes one mean gap after the last op was due
        left = t0 + due[-1] * n / max(n - 1, 1) - time.perf_counter()
        if left > 0:
            time.sleep(left)
    finally:
        if span is not None:
            span.__exit__(None, None, None)
    collector.join(GRACE_S + 5.0)
    return w


def device_array_bytes(devs) -> int:
    """Bytes of the live JAX arrays held on ``devs``, each buffer once:
    the index's pools and whatever else the process keeps on the device,
    without the programs, the allocator's slack or transient buffers."""
    import jax

    seen = {}     # two arrays may share one buffer: count it once
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            if s.device in devs:
                seen[(s.device.id, s.data.unsafe_buffer_pointer())] = \
                    s.data.nbytes
    return sum(seen.values())


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def _answer(res):
    """The client's answer in the reference's terms."""
    if res is None:
        return None
    st = res.status.name
    return ("OK", res.value) if st == "OK" else (st, None)


@dataclasses.dataclass
class CheckResult:
    wrong: int = 0              # answers that differ from the reference
    unanswered: int = 0         # no answer within the grace period
    refused: int = 0            # OVERLOADED
    misaligned: int = 0         # flushed ops that match no submission
    first_fault: Optional[str] = None

    def as_checks(self) -> dict:
        return {"wrong_answers": {"value": self.wrong, "limit": 0},
                "unanswered": {"value": self.unanswered, "limit": 0},
                "misaligned": {"value": self.misaligned, "limit": 0}}

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.as_checks().values())


def check(oracle: Oracle, flushes: list, submissions: list,
          tenant_prefix: bytes, res: CheckResult) -> None:
    """Walk the recorded flushes in order, pair each flushed op with the
    next admitted submission, and compare the answer that submission got
    with the reference's.  ``submissions``: ``(requests, answers, wrong)``
    in submission order, where ``wrong`` is None or a bool array that
    marks the ops answered wrongly.  Refused ops never reach a flush."""
    plen = len(tenant_prefix)
    admitted = []
    for reqs, answers, wrong in submissions:
        for i, (r, a) in enumerate(zip(reqs, answers)):
            if a is not None and a.status.name == "OVERLOADED":
                res.refused += 1
            else:
                admitted.append((r, a, wrong, i))
    k = 0
    for batch, _t0, _t1 in flushes:
        for enc in batch:
            key = get_key(enc)[plen:]
            if k >= len(admitted):
                res.misaligned += 1
                continue
            req, ans, wrong, i = admitted[k]
            k += 1
            if get_key(req) != key:
                res.misaligned += 1
                continue
            got, exp = _answer(ans), oracle.get(key)
            if got is None:
                res.unanswered += 1
            elif got != exp:
                res.wrong += 1
                if wrong is not None:
                    wrong[i] = True
                if res.first_fault is None:
                    res.first_fault = f"get {key!r}: got {got}, reference {exp}"
    for _r, a, _w, _i in admitted[k:]:
        if a is None:
            res.unanswered += 1
        else:
            res.misaligned += 1


# ---------------------------------------------------------------------------
# a session: set-up once, then warm-up and windows
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Phase:
    """One measured window: what was sent, and what came back."""

    stream: ycsb.OpStream
    win: Window
    answers: list
    wrong: np.ndarray                 # filled by Session.check
    flush_ms: np.ndarray              # facade execute wall time per flush
    completed: int                    # ServiceStats.completed over it
    flushes: int                      # ServiceStats.flushes over it
    compiles: int                     # programs made ready during it
    memory_peak_bytes: Optional[int] = None
    array_bytes: int = 0              # live device arrays after it


class Session:
    """The service over the cell's index, the reference beside it, and
    the ops sent so far.  ``check`` replays every flush since the last
    check on the reference; call it after each phase has been answered.

    ``wrap_index(index, corpus, tenant_prefix)`` puts an object in the
    index's place under the service: the control and the fault tests."""

    def __init__(self, cell: Cell, seed: int, *, require_chip: bool = True,
                 wrap_index: Optional[Callable] = None,
                 cache_dir: str = CACHE_DIR):
        import jax

        # libtpu logs under /tmp unless told otherwise: keep them in the
        # checkout, beside the traces
        logs = os.environ.setdefault(
            "TPU_LOG_DIR", os.path.join(BENCH_DIR, "out", "tpu_logs"))
        os.makedirs(logs, exist_ok=True)
        self.cell, self.seed = cell, seed
        self.devs = (check_devices(cell.chips) if require_chip
                     else jax.devices()[: cell.chips])
        self.cache_path = enable_compile_cache(cache_dir)
        self.memory: dict = {}
        self.note_memory("start")
        self.counter = CompileCounter()
        from repro.serve.service import IndexService, ServiceConfig

        conf = cell.config
        self.tenant = conf["tenant"]
        self.prefix = IndexService.encode_key(self.tenant, b"")
        loaded = load_index(cell, cache_dir)
        self.corpus, self.index, self.times = (loaded.corpus, loaded.index,
                                               loaded.times)
        self.note_memory("load")
        self.memory["index_nbytes"] = int(self.index.nbytes())
        log(f"keys: length {corpus_mod.key_stats(self.corpus)}")
        log(f"load: {self.times}, {self.index.n_entries} keys, width "
            f"{self.index.width}, compile cache {self.cache_path}")
        inner = (self.index if wrap_index is None
                 else wrap_index(self.index, self.corpus, self.prefix))
        self.rec = RecordingIndex(inner)
        self.svc = IndexService(self.rec, ServiceConfig(**conf["service"]))
        self.submissions: list = []
        self.result = CheckResult()
        self._oracle: Optional[Oracle] = None

    def note_memory(self, when: str) -> None:
        """The allocator's bytes in use and the live arrays' bytes, for the
        log: what the index holds against what programs and buffers add."""
        stats = self.devs[0].memory_stats() or {}
        self.memory[when] = {"bytes_in_use": stats.get("bytes_in_use"),
                             "arrays": device_array_bytes(self.devs)}

    @property
    def oracle(self) -> Oracle:
        """The reference, built at the first check (after the window)."""
        if self._oracle is None:
            self._oracle = Oracle(self.corpus.key_list(),
                                  self.corpus.values.tolist())
        return self._oracle

    def close(self) -> None:
        self.svc.close()

    def _execute(self, batch: list) -> list:
        answers = self.svc.execute(batch, self.tenant, timeout=900)
        self.submissions.append((batch, answers, None))
        return answers

    def warm(self) -> None:
        """Every program shape the traffic can meet (each get-group size up
        to ``get_max_group``), then the cell's own traffic at its rate for
        ``warmup_seconds``."""
        from repro.index import GetRequest

        traffic = self.cell.traffic
        t, c0 = time.perf_counter(), self.counter.count
        rng = ycsb.seed_rng(self.seed, "sweep")
        keys = [self.corpus.key(i)
                for i in rng.integers(0, len(self.corpus), 1024)]
        for c in range(1, traffic["warm_shapes"]["get_max_group"] + 1):
            self._execute([GetRequest(k) for k in keys[:c]])
        self.times["warm_shapes_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.window(traffic["warmup_seconds"], self.seed, "warmup")
        self.times["warm_replay_s"] = time.perf_counter() - t
        self.times["warm_programs"] = self.counter.count - c0
        self.note_memory("warm")
        log(f"warm-up: {self.counter.count - c0} programs made ready "
            f"({self.counter.hits} cache hits, {self.counter.misses} misses)"
            f", shapes {self.times['warm_shapes_s']:.1f} s, replay "
            f"{self.times['warm_replay_s']:.1f} s")

    def window(self, seconds: float, seed: int, stream_name: str = "window",
               rate: Optional[float] = None,
               trace_span: Optional[str] = None) -> Phase:
        traffic = self.cell.traffic
        if rate is not None:
            traffic = dict(traffic, rate_ops_per_s=rate)
        stream = ycsb.make_stream(traffic, len(self.corpus), seconds, seed,
                                  stream_name)
        reqs = build_requests(stream, self.corpus)
        # collect set-up's garbage now and exempt what survives (the
        # corpus, the requests, earlier answers) from later collections.
        # The window keeps every future, answer and flushed batch for the
        # check, so the collector's full passes over them would grow
        # through the window (11 ms after 4 s to 145 ms after 43 s, on a
        # TPU v5e host) and pause every thread: it stays off until the
        # window has closed
        gc.collect()
        gc.freeze()
        st0, c0, n0 = self.svc.stats(), self.counter.count, \
            len(self.rec.flushes)
        gc.disable()
        try:
            win = run_window(self.svc, self.tenant, reqs, stream.due,
                             trace_span)
        finally:
            gc.enable()
        compiles = self.counter.count - c0
        st1 = self.svc.stats()
        mem = (self.devs[0].memory_stats() or {}).get("peak_bytes_in_use")
        self.note_memory("window")
        flush_ms = np.array([(t1 - t0) * 1e3 for _b, t0, t1
                             in self.rec.flushes[n0:]])
        answers = win.answers()
        wrong = np.zeros(len(reqs), bool)
        self.submissions.append((reqs, answers, wrong))
        return Phase(stream, win, answers, wrong, flush_ms,
                     st1.completed - st0.completed, st1.flushes - st0.flushes,
                     compiles, mem, device_array_bytes(self.devs))

    def check(self) -> CheckResult:
        """Replay every flush recorded since the last check."""
        check(self.oracle, self.rec.take_flushes(), self.submissions,
              self.prefix, self.result)
        self.submissions = []
        return self.result

    @property
    def live_keys(self) -> int:
        return len(self.oracle.kv)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    """What the metric readers see (``metrics/<name>.py``)."""

    seconds: float
    setup_s: float
    latency_ms: np.ndarray       # per window op; inf when it failed
    gen_lag_ms: np.ndarray       # per window op
    correct_by_close: int        # ops answered correctly by the close
    flush_ms: np.ndarray         # facade execute wall time per flush
    completed: int               # ServiceStats.completed over the window
    flushes: int                 # ServiceStats.flushes over the window
    compiles_in_window: int
    memory_peak_bytes: Optional[int]
    array_bytes: int             # live device arrays after the window
    live_keys: int
    trace: Optional[object] = None   # trace.TraceSummary, --trace 1 only


def run_data(phase: Phase, seconds: float, setup_s: float,
             live_keys: int) -> RunData:
    win = phase.win
    lat = (win.done - (win.t0 + phase.stream.due)) * 1e3
    failed = (np.isnan(lat) | phase.wrong | np.array(
        [a is not None and a.status.name not in ("OK", "NOT_FOUND")
         for a in phase.answers]))
    lat = np.where(failed, np.inf, lat)
    by_close = ~failed & (win.done <= win.t0 + seconds)
    return RunData(
        seconds=seconds, setup_s=setup_s, latency_ms=lat,
        gen_lag_ms=(win.submitted - (win.t0 + phase.stream.due)) * 1e3,
        correct_by_close=int(by_close.sum()), flush_ms=phase.flush_ms,
        completed=phase.completed, flushes=phase.flushes,
        compiles_in_window=phase.compiles,
        memory_peak_bytes=phase.memory_peak_bytes,
        array_bytes=phase.array_bytes, live_keys=live_keys)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             wrap_index: Optional[Callable] = None,
             cache_dir: str = CACHE_DIR, trace_dir: Optional[str] = None):
    """One run; returns (result dict, check lines for standard error)."""
    s = Session(cell, seed, require_chip=require_chip, wrap_index=wrap_index,
                cache_dir=cache_dir)
    try:
        s.warm()
        if trace:
            import jax.profiler as prof

            trace_dir = trace_dir or os.path.join(BENCH_DIR, "out", "trace",
                                                  cell.name)
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = prof.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            prof.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        if trace:
            # a traced window is kept short: a TPU trace holds about a
            # thousand op events per search program, and writing and
            # reading it grows with the window
            seconds = min(seconds, TRACE_WINDOW_S)
        try:
            phase = s.window(seconds, seed,
                             trace_span=trace_mod.WINDOW_SPAN if trace
                             else None)
        finally:
            if trace:
                t = time.perf_counter()
                prof.stop_trace()
                s.times["trace_stop_s"] = time.perf_counter() - t
    finally:
        s.close()
    # the reference, once the window has closed and the service is gone
    t = time.perf_counter()
    res = s.check()
    check_s = time.perf_counter() - t
    data = run_data(phase, seconds, setup_s, s.live_keys)
    if trace:
        t = time.perf_counter()
        path = trace_mod.find_xplane(trace_dir)
        if path is not None:
            data.trace = trace_mod.summarize(trace_mod.read_xplane(path))
        shutil.rmtree(trace_dir, ignore_errors=True)
        s.times["trace_read_s"] = time.perf_counter() - t
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(data)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    log(f"window: {len(phase.answers)} ops over {seconds} s, "
        f"{phase.flushes} flushes, {phase.compiles} programs made ready in "
        f"the window; check {check_s:.1f} s; set-up {s.times}; device "
        f"memory {s.memory}; host peak RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f} GiB")
    if res.first_fault:
        log(f"first wrong answer: {res.first_fault}")
    dev = s.devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(s.devs),
              "memory_peak_bytes": phase.memory_peak_bytes}
    failed = int(np.sum(~np.isfinite(data.latency_ms)))
    out = {"correct": res.correct, "attempted": len(phase.answers),
           "failed": failed, "metrics": metrics, "device": device}
    if trace and data.trace is not None:
        device["busy_s"] = data.trace.busy_s
        device["window_s"] = data.trace.window_s
        out["breakdown"] = {
            "device_ops": sorted(data.trace.program_s.items(),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": sorted(data.trace.idle_by_span.items(),
                                key=lambda x: -x[1])[:10],
        }
    out["checks"] = res.as_checks()
    lines = [f"check {k}: {v['value']} (limit {v['limit']})"
             for k, v in out["checks"].items()]
    return out, lines


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        raise SystemExit("--seed must be >= 0 and --seconds > 0")
    cell = load_cell(args.workload)
    out, lines = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start)
    for line in lines:
        log(line)
    print(json.dumps(out), flush=True)
    return 0
