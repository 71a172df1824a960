"""Drive ``GenerationService`` in-process, open loop or closed loop.

The objects are the ones ``serve.py``'s HTTP handlers call: the service,
its ``DecodeEngine``, the model step, the kernels.  No daemon, no port,
no checkpoint, no child process: the one process that holds the chip
builds the weights from the seed, hands them over, warms the cell's
shapes with real requests through the live engine, and offers load from
one generator thread.

A request's clock starts when it is DUE (open loop) or when its client
is free to send (closed loop), not when the generator got round to it,
and stops at events of its own stream: the service calls ``put`` on the
object handed to ``submit(stream=...)`` as each token lands.
"""

from __future__ import annotations

import queue
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import cells, stats, traffic
from benchmark import weights as W


class Stream:
    """What a client of the stream sees of one request: when the first
    and the last token landed, and how many."""

    __slots__ = ("t_first", "t_last", "n")

    def __init__(self):
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.n = 0

    def put(self, item) -> None:
        if item is None:
            return
        now = time.perf_counter()
        if self.t_first is None:
            self.t_first = now
        self.t_last = now
        self.n += 1


class Req:
    __slots__ = ("ids", "n_new", "due", "sent", "stream", "future", "done",
                 "result", "error")

    def __init__(self, ids, n_new, due=None):
        self.ids, self.n_new, self.due = ids, n_new, due
        self.sent = None
        self.stream = Stream()
        self.future = None
        self.done: Optional[float] = None
        self.result = None
        self.error: Optional[BaseException] = None


# what the benchmark sets itself, and what needs live objects a data
# file cannot hold (a sharded service is a kind of traffic of its own)
SET_HERE = ("seed", "metrics_history_interval")
NOT_FROM_A_FILE = ("mesh", "dist")


def service_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's ``service`` mapping WHOLE, as keyword
    arguments of ``GenerationService``: ``kv_layout``, ``prefix_cache``,
    ``engine_pipeline_depth`` and whatever else a later configuration
    sets reach the engine, and a key the service does not know is a
    TypeError there, never dropped.  JSON lists become tuples."""
    svc = {k: tuple(v) if isinstance(v, list) else v
           for k, v in cfg["service"].items()}
    refused = sorted(set(svc) & set(SET_HERE + NOT_FROM_A_FILE))
    if refused:
        raise SystemExit(
            f"service keys {refused}: {SET_HERE} are the benchmark's to "
            f"set, {NOT_FROM_A_FILE} need objects no data file holds"
        )
    svc.setdefault("batcher", "continuous")
    return svc


def build_service(cell, seed: int, log):
    """Weights from the seed, then the service exactly as
    ``serve.load_service`` would build it from the configuration's
    mapping — minus the checkpoint and its ``PRNGKey(0)``."""
    import jax
    import jax.numpy as jnp

    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.serve import GenerationService

    cfg = cell.config
    arch = cells.architecture(cfg)
    d = arch.dims_of(cfg)
    t0 = time.perf_counter()
    model = create_model(dict(cfg["model"]))
    params = W.program_params(arch, seed, d, jnp.bfloat16)
    jax.block_until_ready(params)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    W.check_layout(params, abstract)
    t1 = time.perf_counter()
    log("setup.init_weights_s", t1 - t0)
    service = GenerationService(
        model, {"params": params},
        seed=int(seed) & 0x7FFFFFFF,
        metrics_history_interval=None,
        **service_kwargs(cfg),
    )
    del params
    log("setup.build_service_s", time.perf_counter() - t1)
    return service


def submit(service, req: Req, on_done=None) -> None:
    req.sent = time.perf_counter()
    fut = service.submit(
        req.ids, req.n_new, temperature=0.0, logprobs=True,
        stream=req.stream,
    )
    req.future = fut

    def finished(f):
        req.done = time.perf_counter()
        err = f.exception()
        if err is not None:
            req.error = err
        else:
            req.result = f.result()
        if on_done is not None:
            on_done(req)

    fut.add_done_callback(finished)


def warm(service, cell, seed: int, log) -> None:
    """Compile every program the cell's traffic reaches, through the
    live engine: per prompt bucket one request alone (staged prefill,
    insert, plain dispatch), then one joining a running decode (the
    fused prefill+decode dispatch), then a burst that fills the slots."""
    mix = cell.traffic
    buckets = list(mix["warm_buckets"])
    vocab = int(cell.config["vocab_size"])
    rng = np.random.default_rng([int(seed), 0x3A33])
    # decode steps a dispatch: the configuration's pinned K, or what the
    # mix says where K is not one number (an adaptive ladder)
    k = cell.config["service"].get("steps_per_dispatch")
    k = k if isinstance(k, int) else int(mix["warm_steps_per_dispatch"])
    cap = int(cell.config["service"]["max_new_buckets"][-1])

    def mk(n_prompt, n_new):
        return Req(rng.integers(1, vocab, size=n_prompt).tolist(),
                   min(n_new, cap))

    t0 = time.perf_counter()
    for i, b in enumerate(buckets):
        lone = mk(b, 2 * k)
        submit(service, lone)
        lone.future.result(timeout=1800)
        log(f"setup.warm_alone_{b}_s", time.perf_counter() - t0)
        t0 = time.perf_counter()
        runner = mk(buckets[0], 16 * k)
        submit(service, runner)
        while runner.stream.n == 0 and not runner.future.done():
            time.sleep(0.005)
        joiner = mk(b, 2 * k)
        submit(service, joiner)
        joiner.future.result(timeout=1800)
        runner.future.result(timeout=1800)
        log(f"setup.warm_joined_{b}_s", time.perf_counter() - t0)
        t0 = time.perf_counter()
    slots = int(cell.config["service"]["batch_sizes"][-1])
    burst = [mk(buckets[0], 4 * k) for _ in range(slots + 2)]
    for r in burst:
        submit(service, r)
    for r in burst:
        r.future.result(timeout=1800)
    log("setup.warm_burst_s", time.perf_counter() - t0)


def open_loop(service, cell, seed: int, seconds: float, vocab: int,
              annotate) -> Dict[str, Any]:
    mix = cell.traffic
    due = traffic.arrivals(float(mix["rate_per_s"]), seconds,
                           int(mix["schedule_seed"]))
    reqs_in = traffic.requests(mix, len(due), vocab, seed)
    reqs = [Req(r["ids"], r["n_new"], float(t)) for r, t in zip(reqs_in, due)]
    t0 = time.perf_counter()
    for r in reqs:
        wait = t0 + r.due - time.perf_counter()
        if wait > 0:
            with annotate("bench.sleep_until_due"):
                time.sleep(wait)
        with annotate("bench.submit"):
            submit(service, r)
    end = t0 + seconds
    with annotate("bench.wait_window"):
        time.sleep(max(0.0, end - time.perf_counter()))
    return {"t0": t0, "t_end": end, "reqs": reqs}


def closed_loop(service, cell, seed: int, seconds: float, vocab: int,
                annotate) -> Dict[str, Any]:
    mix = cell.traffic
    clients = int(mix["clients"])
    pool = traffic.requests(mix, int(mix["request_pool"]), vocab, seed)
    free: "queue.Queue[int]" = queue.Queue()
    reqs: List[Req] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    nxt = 0
    for c in range(clients):
        free.put(c)
    while True:
        left = end - time.perf_counter()
        if left <= 0:
            break
        try:
            with annotate("bench.wait_client"):
                free.get(timeout=min(left, 0.25))
        except queue.Empty:
            continue
        spec = pool[nxt % len(pool)]
        nxt += 1
        r = Req(spec["ids"], spec["n_new"], time.perf_counter() - t0)
        reqs.append(r)
        with annotate("bench.submit"):
            submit(service, r, on_done=lambda _r: free.put(0))
    return {"t0": t0, "t_end": end, "reqs": reqs}


def drain(reqs: List[Req], timeout_s: float) -> None:
    """Follow every request sent in the window to its end (no new load)."""
    stop = time.perf_counter() + timeout_s
    for r in reqs:
        if r.future is None:
            continue
        try:
            r.future.exception(timeout=max(0.0, stop - time.perf_counter()))
        except Exception:  # timeout: the request stays unfinished
            pass


def reduce_window(win: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """End-to-end numbers of one window, and what the lines before the
    result show: lateness, sent / succeeded / failed."""
    t0, t_end, reqs = win["t0"], win["t_end"], win["reqs"]
    far = (time.perf_counter() - t0) * 1e3
    ttft, tpot = [], []
    ok = failed = unfinished = tokens_in_window = done_in_window = 0
    for r in reqs:
        if r.error is not None:
            failed += 1
            ttft.append(far)
            continue
        if r.done is None:
            unfinished += 1
            ttft.append(far if r.stream.t_first is None
                        else (r.stream.t_first - t0 - r.due) * 1e3)
            continue
        ok += 1
        ttft.append((r.stream.t_first - t0 - r.due) * 1e3)
        n = len(r.result["ids"])
        if n >= 8:
            tpot.append((r.stream.t_last - r.stream.t_first) * 1e3 / (n - 1))
        if r.done <= t_end:
            done_in_window += 1
            tokens_in_window += n
    out = {
        "sent": len(reqs), "succeeded": ok, "failed": failed,
        "unfinished_after_drain": unfinished,
        "completed_in_window": done_in_window,
        "lateness": stats.lateness(
            [r.due for r in reqs], [r.sent - t0 for r in reqs]
        ),
        "serve_tokens_per_s": tokens_in_window / seconds,
    }
    if ttft:
        out["ttft_p50_ms"] = stats.percentile(ttft, 50)
        out["ttft_p90_ms"] = stats.percentile(ttft, 90)
    if tpot:
        out["tpot_p50_ms"] = stats.percentile(tpot, 50)
        out["tpot_p90_ms"] = stats.percentile(tpot, 90)
    return out


def sample_finished(reqs: List[Req], n: int, seed: int) -> List[Dict[str, Any]]:
    """A seeded sample of the requests the window finished, the longest
    (prompt plus served tokens) always in it."""
    done = [r for r in reqs if r.result is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.ids) + len(r.result["ids"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x5A3F])
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(0, n - 1)]]
    return [
        {"ids": r.ids, "out": r.result["ids"],
         "logprobs": r.result["logprobs"]}
        for r in [longest] + pick
    ]


def run_serve(loop: str, cell, seed: int, seconds: float, trace: bool,
              control: bool, dev: Dict[str, Any], t_start: float
              ) -> Dict[str, Any]:
    import gc

    import jax

    from benchmark import device as D
    from benchmark import harness as H
    from benchmark.reference.check_serve import serve_readings

    log = H.log
    mix, cfg = cell.traffic, cell.config
    vocab = int(cfg["vocab_size"])
    counter = H.LowerCounter()
    service = build_service(cell, seed, log)
    log("memory.after_build", D.memory(cell.chips))
    warm(service, cell, seed, log)
    mem_open = D.memory(cell.chips)
    log("memory.after_warm", mem_open)
    stats0 = service.stats()
    sl = H.TracedSlice.steady(trace, seconds, mix)
    watch = H.GcWatch()
    watch.settle()
    setup_s = time.perf_counter() - t_start
    log("setup.compile", counter.totals)
    log("setup_s", setup_s)
    with counter.window(), watch.window():
        t0 = time.perf_counter()
        sl.start(t0)
        drive = open_loop if loop == "open" else closed_loop
        win = drive(service, cell, seed, seconds, vocab, sl.annotate)
        lowered = counter.n
    log("programs_lowered_in_window", lowered)
    H.mark("window_closed")
    drain(win["reqs"], float(mix.get("drain_timeout_s", 60.0)))
    stats1 = service.stats()
    e2e = reduce_window(win, seconds)
    e2e["setup_s"] = setup_s
    for k in ("sent", "succeeded", "failed", "unfinished_after_drain",
              "completed_in_window", "lateness"):
        log(f"requests.{k}", e2e[k])
    for k in ("ttft_p50_ms", "ttft_p90_ms", "tpot_p50_ms", "tpot_p90_ms",
              "serve_tokens_per_s"):
        if k in e2e:
            log(k, e2e[k])
    H.mark("drained")
    tr = sl.load()
    H.mark("trace_read")
    events = service.trace()["traceEvents"] if trace else []
    mem = D.memory(cell.chips)
    log("memory.after_window", mem)
    samples = sample_finished(win["reqs"], int(mix["check_requests"]), seed)
    service.close()
    del service
    gc.collect()
    t_ref = time.perf_counter()
    pad_len = int(cfg["service"]["prompt_buckets"][-1]) + int(
        cfg["service"]["max_new_buckets"][-1]
    )
    ok = H.compare("programs_lowered_in_window", lowered, 0)
    ok = H.compare("nothing_to_compare", int(not samples), 0) and ok
    if samples:
        readings = serve_readings(cfg, seed, samples, pad_len, control=control)
        log("correct.tokens_compared", readings["tokens_compared"])
        ok = H.judge(readings, mix["limits"]) and ok
        for k, v in readings.items():
            if k.startswith("control"):
                log(k, v)
    log("reference_s", time.perf_counter() - t_ref)
    H.mark("reference_done")
    ctx = {
        "trace": tr, "slice_s": sl.length_s, "slice": (sl.t_lo, sl.t_hi),
        "cell": cell, "peaks": dev["peaks"], "window": win, "e2e": e2e,
        "stats0": stats0, "stats1": stats1, "events": events,
    } if trace else None
    return H.result_line(
        cell, dev, trace, ok, attempted=e2e["sent"],
        failed=e2e["failed"] + e2e["unfinished_after_drain"], e2e=e2e,
        layer_ctx=ctx, memory_peak=mem["peak"],
        memory_steady=max(mem_open["in_use"], mem["in_use"]),
    )

