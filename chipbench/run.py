#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

One process on the TPU it is started on; it starts no children. It builds
the server through `launch/serve.parse_args` / `resolve_stages` /
`build_server` with `--impl pallas` (LimeServer -> ContinuousBatching-
Scheduler -> EngineBackend -> InterleavedEngine), with weights it makes
from the seed (`weights.py`). Set-up warms every prompt length of the
cell's traffic, then the cell's clients drive the scheduler step by step
on the wall clock; with a ramp, the window opens after that many requests
have finished. The window lasts `--seconds`. Afterwards the tokens served
in the window are checked against the float32 reference (`reference.py`)
and the last line of stdout is the result as one JSON object. With
`--trace 1` the last seconds of the window are traced with jax.profiler
and the per-layer metrics are printed instead of the end-to-end ones.

Exit codes: 0 with a result; 1 when the run failed; 2 when the cell or
the chip is not there (no result is printed).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse        # noqa: E402
import gc              # noqa: E402
import json            # noqa: E402
import shutil          # noqa: E402
import sys             # noqa: E402
import tempfile        # noqa: E402
from contextlib import contextmanager, nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"
TRACE_SECONDS = 12.0         # traced tail of the window
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def fail(msg: str, code: int = 2):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Run:
    """What a metric reader gets: the window, the trace, the cell."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


@contextmanager
def weights_from(params):
    """build_server draws its own random params; for this call it is
    handed the benchmark's (the same tree, made from the seed)."""
    from repro.models import model as M
    orig = M.init_params
    M.init_params = lambda cfg, key: params
    try:
        yield
    finally:
        M.init_params = orig


class Spans:
    """The benchmark's own spans around each call into a layer: profiler
    annotations (named "cb.<layer>.<call>") in the traced run."""

    def __init__(self, on: bool):
        import jax
        self.on = on
        self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann("cb." + name) if self.on else nullcontext()

    def wrap(self, obj, attr: str, name: str) -> None:
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with self._ann("cb." + name):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)


def device_info(devs, chips: int) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": int(peak)}


def serve_once(sched, req) -> None:
    sched.submit(req)
    while not req.done and not req.rejected:
        sched.step()


def drive(sched, backend, clients, deck, *, seed: int, vocab: int,
          seconds: float, ramp: int, spans: Spans, trace_at=None):
    """The clients against the scheduler, one step at a time, until the
    window closes. A request is submitted only once its send time has
    come. Returns the window and the monotonic time it opened at."""
    import heapq

    from chipbench.traffic import prompt_tokens
    from chipbench.window import Sent, Window
    from repro.serving.scheduler import Request

    clock = backend.now
    due = list(clients.start(clock()))
    heapq.heapify(due)
    sent, live, steps = [], [], []
    opened = clock() if ramp == 0 else None
    opened_mono = time.monotonic()
    done = 0
    traced = False
    while True:
        now = clock()
        if opened is not None:
            if now >= opened + seconds:
                break
            if trace_at is not None and not traced \
                    and now >= opened + seconds - trace_at[0]:
                trace_at[1]()
                traced = True
        while due and due[0][0] <= now:
            t_send, client = heapq.heappop(due)
            k = len(sent)
            shape = deck[k % len(deck)]
            s = Sent(k, client, prompt_tokens(seed, k, shape.prompt_len,
                                              vocab), shape.max_new, t_send)
            s.request = Request(k, s.prompt, shape.max_new,
                                arrival_s=t_send)
            sched.submit(s.request)
            sent.append(s)
            live.append(s)
        if not (sched.has_live_work or sched.next_pending_s is not None):
            wait = due[0][0] - now
            if opened is not None:
                wait = min(wait, opened + seconds - now)
            with spans("client.wait"):
                time.sleep(max(wait, 0.0))
            continue
        decoding = sched.in_flight > 0
        ctx = [len(s.prompt) + len(s.request.output) for s in live
               if s.request.admitted_s is not None]
        t0 = clock()
        with spans("sched.step"):
            sched.step()
        t = clock()
        if decoding:
            steps.append((t0, t, "decode", ctx))
        else:
            steps.append((t0, t, "prefill",
                          [len(s.prompt) for s in live
                           if s.request.admitted_s is not None
                           and s.request.admitted_s >= t0]))
        for s in live:
            r = s.request
            new = len(r.output) - len(s.token_s)
            s.token_s.extend([t] * new)
            if r.done or r.rejected:
                s.done_s, s.failed = t, bool(r.rejected)
                done += 1
                for nxt in clients.finished(s.client, t):
                    heapq.heappush(due, nxt)
                if opened is None and done >= ramp:
                    opened, opened_mono = t, time.monotonic()
        live = [s for s in live if s.done_s is None]
    return Window(sent, opened, opened + seconds, steps), opened_mono


def check(d, seed: int, w, cell: dict, max_len: int, *,
          control: bool = False) -> dict:
    """The comparison that decides `correct` (see reference.py): a seeded
    sample of the requests finished in the window, the one with the most
    served tokens always in it. With `control`, also the fp8 control's
    reading on the same prompts and tokens (control.py)."""
    import numpy as np

    from chipbench import reference, window as W
    done = W.finished(w)
    n = cell["check"]["requests"]
    pick = []
    if done:
        longest = max(done, key=lambda s: len(s.output))
        rest = [s for s in done if s is not longest]
        rng = np.random.default_rng([int(seed) % (1 << 64), 3])
        pick = [longest] + [rest[i] for i in rng.permutation(len(rest))
                            [:max(n - 1, 0)]]
    seqs = reference.request_sequences([s.prompt for s in pick],
                                       [s.output for s in pick])
    rows = reference.forward_rows(d, seed, seqs, max_len, control=control)
    ref, ctl = rows if control else (rows, None)
    gaps = [reference.served_gaps(r, np.asarray(s.output))
            for r, s in zip(ref, pick)]
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {"logit_gap": float(flat.max()) if flat.size else float("inf"),
           "tokens_compared": int(flat.size),
           "requests_compared": len(pick)}
    if control:
        out["control_gap"] = float(max(
            (reference.control_gaps(r, c).max() for r, c in zip(ref, ctl)),
            default=float("nan")))
    return out


def run(argv=None) -> int:
    args = parse_args(argv)
    if not (SRC / "repro" / "launch" / "serve.py").is_file():
        fail(f"no program under {SRC}: run from a checkout of the "
             f"repository")
    sys.path[:0] = [str(REPO), str(SRC)]
    from chipbench import manifest
    try:
        parts = manifest.resolve(args.workload, bool(args.trace))
    except manifest.ManifestError as e:
        fail(str(e))
    chips = parts["entry"]["chips"]

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {devs[0].platform!r}); the "
             f"benchmark has no CPU fallback")
    if len(devs) < chips:
        fail(f"{args.workload} needs {chips} TPU chips; {len(devs)} exist")
    peaks = json.loads(PEAKS.read_text())
    if devs[0].device_kind not in peaks:
        fail(f"no peaks for device kind {devs[0].device_kind!r} in {PEAKS}")
    result = run_cell(args, parts, devs, peaks[devs[0].device_kind])
    ck = result["check"]
    for k, v in ck.items():
        print(f"check: {k} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(args, parts, devs, peak: dict, *, control: bool = False) -> dict:
    import jax
    import numpy as np

    from chipbench import traffic as T, weights, window as W
    from chipbench.model import dims, model_config
    from repro.launch import serve as S
    from repro.launch.compile_cache import compile_stats, enable_compile_cache
    from repro.serving import ContinuousBatchingScheduler, SchedulerConfig
    from repro.serving.scheduler import Request

    entry, tr, cell = parts["entry"], parts["traffic"], parts["cell"]
    chips, seed = entry["chips"], args.seed
    split = {"jax_s": time.monotonic() - T_START}
    enable_compile_cache()
    # every program in the cache after the first run, however quick
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    d = dims(parts["config"])
    cfg = model_config(d)
    max_len = T.max_len(tr)
    t = time.monotonic()
    params = weights.program_params(d, seed)
    jax.block_until_ready(params)
    split["weights_s"] = time.monotonic() - t

    t = time.monotonic()
    sargs = S.parse_args([
        "--arch", d.name, "--stages", str(chips), "--impl", "pallas",
        "--pattern", tr["pattern"], "--max-len", str(max_len),
        "--seed", str(seed % (1 << 31))])
    S.resolve_stages(sargs, len(devs))
    with weights_from(params):
        srv = S.build_server(cfg, sargs)
    if srv.engine is None or srv.engine.impl != "pallas":
        raise RuntimeError("build_server built no Pallas engine")
    backend = srv.make_backend()
    sched = ContinuousBatchingScheduler(backend, SchedulerConfig())
    split["build_s"] = time.monotonic() - t

    t = time.monotonic()
    sched.begin([])
    for i, L in enumerate(T.prompt_lengths(tr)):
        toks = np.random.default_rng([seed % (1 << 64), 500 + i]).integers(
            1, d.vocab, L, dtype=np.int32)
        serve_once(sched, Request(-1 - i, toks, 3, arrival_s=backend.now()))
    split["warmup_s"] = time.monotonic() - t
    split["setup_peak_bytes"] = device_info(devs, chips)["memory_peak_bytes"]

    spans = Spans(bool(args.trace))
    eng = srv.engine
    if args.trace:
        for obj, attr, name in (
                (backend, "start_batch", "backend.start_batch"),
                (backend, "_prefill", "backend.prefill"),
                (backend, "_sample", "backend.sample"),
                (eng, "init_state", "engine.init_state"),
                (eng, "seed_cache", "engine.seed_cache"),
                (eng, "decode_requests", "engine.decode"),
                (eng, "_fetch", "engine.fetch"),
                (eng, "_step", "engine.step")):
            spans.wrap(obj, attr, name)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    tracing = {}

    def start_trace():
        jax.profiler.start_trace(trace_dir)
        tracing["ann"] = jax.profiler.TraceAnnotation("cb.traced")
        tracing["ann"].__enter__()
        tracing["open_s"] = backend.now()

    clients = parts["client"].make(tr)
    c0 = compile_stats()
    t = time.monotonic()
    w, open_mono = drive(
        sched, backend, clients, T.deck(tr), seed=seed, vocab=d.vocab,
        seconds=args.seconds, ramp=tr.get("ramp_requests", 0), spans=spans,
        trace_at=(min(TRACE_SECONDS, args.seconds), start_trace)
        if args.trace else None)
    split["ramp_s"] = open_mono - t
    setup_s = open_mono - T_START
    c1 = compile_stats()
    trace = None
    if args.trace:
        tracing["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    device = device_info(devs, chips)
    gc.collect()
    in_use = max((x.memory_stats() or {}).get("bytes_in_use", 0)
                 for x in devs[:chips])

    # free the program's state before the reference runs beside it
    del srv, backend, sched, eng, params, spans
    gc.collect()

    if args.trace:
        from chipbench import tracereduce as R
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        trace = R.load_xplane(str(path))
        print("chipbench: trace planes " + json.dumps(
            R.plane_summary(str(path))), file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
        traced = trace.span("cb.traced")
        if not traced:
            raise RuntimeError("the trace holds no cb.traced span")
        within = [(traced[0][1], traced[0][2])]

    run_rec = Run(window=w, trace=trace, dims=d, chips=chips, peak=peak,
                  setup_s=setup_s, device=device, hbm_in_use_bytes=in_use,
                  traced_open_s=tracing.get("open_s"),
                  traced=within if args.trace else None)
    metrics = {}
    for m, mod in parts["metrics"]:
        v = mod.read(run_rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if args.trace:
        from chipbench import tracereduce as R
        device["busy_s"] = float(np.mean(
            [R.length(R.busy(trace, k, within)) for k in range(chips)]))
        device["window_s"] = within[0][1] - within[0][0]
        bd = R.breakdown(trace, within)

    t = time.monotonic()
    ck = check(d, seed, w, cell, max_len, control=control)
    split["check_s"] = time.monotonic() - t
    split["window_compiles"] = c1["requests"] - c0["requests"]
    print("chipbench: set-up split " + json.dumps(split), file=sys.stderr)
    correct, compared = judge(ck["logit_gap"], ck["tokens_compared"],
                              W.failed(w), cell["check"])
    out = {"correct": correct, "attempted": W.attempted(w),
           "failed": W.failed(w), "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = bd
    out["check"] = compared
    if control:
        # the control in the program's place, judged by the same rule
        ok, cmp = judge(ck["control_gap"], ck["tokens_compared"], 0,
                        cell["check"])
        out["control"] = {"correct": ok, "check": cmp}
    return out


def judge(logit_gap: float, tokens: int, failed: int, lim: dict):
    """`correct`, and each number compared beside its limit: no request
    failed, enough served tokens were compared, and no served token's
    logit lies further below the reference's best than the limit."""
    ok = (failed == 0 and tokens >= lim["min_tokens"]
          and logit_gap <= lim["logit_gap"])
    return bool(ok), {
        "logit_gap": {"value": logit_gap, "limit": lim["logit_gap"]},
        "tokens_compared": {"value": tokens, "limit": lim["min_tokens"]}}


if __name__ == "__main__":
    sys.exit(run())
