"""One run of one cell: build the served model from the seed, warm every
shape the cell's traffic can use, drive ``repro.launch.serve.Server``
open-loop for the window, then judge what it served against the plain
reference.

The harness takes from the system only the server (``submit``/``step``),
its counters and host-side mirrors (decode steps, waves, slot lengths),
and its program and kernel names; traffic, weights, the reference, the
trace reduction and the arithmetic of every metric live in the
benchmark's own directory. In the traced stretch alone it wraps the
pool's decode dispatch to log each step's live contexts.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from registry import Registry, load_benchmark, metrics_for
import traffic

# A request still unfinished this long after the window closed has failed.
DRAIN_LIMIT_S = 60.0
# The traced stretch: from a third of the window, at most this long.
TRACE_MAX_S = 8.0
# Worker threads that compile the finish-time gathers during warm-up.
GATHER_THREADS = 8
# Warm-up: every wave size at prefill buckets holding this share of the
# window's prompts, waves of up to RARE_ROWS rows at the others.
FULL_SHARE = 0.2
RARE_ROWS = 16


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Backend compilations (count and seconds), from JAX's own
    monitoring events; tracing and lowering stay in the wall time."""

    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += duration


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def pct(xs, q: float) -> float:
    """The q-th percentile (linear between order statistics)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


class RunView:
    """What a per-layer metric reader sees."""

    def __init__(self, reg, counts, trace, steps, model, family, peaks):
        self.counts, self.trace, self.steps = counts, trace, steps
        self.model, self.family, self.peaks = model, family, peaks
        self._reg = reg
        self.notes: dict = {}

    def kernel(self, name):
        return self._reg.kernel(name)

    def note(self, key, value):
        self.notes[key] = value


def _model_spec(cfg, conf: dict, params_shape, check: bool) -> dict:
    """The configuration as run, for the reference. With ``check`` every
    size in the config file must be what the system's config holds."""
    spec = {k: getattr(cfg, k) for k in conf["model"]}
    bad = {k: (spec[k], v) for k, v in conf["model"].items() if spec[k] != v}
    if check and bad:
        raise ValueError(f"config {conf['name']}: system vs benchmark file "
                         f"differ in {bad}")
    spec["vocab_padded"] = int(params_shape["embed"].shape[0])
    return spec


def make_params(ref, spec, seed: int, like):
    """The served weights, made on the device from the seed in one jitted
    call, in the layout (and dtype) the system reads."""
    import jax
    key = jax.random.PRNGKey(int(seed) % (2 ** 62))
    params = jax.jit(lambda k: ref.init_params(spec, k))(key)
    want = jax.tree.structure(like)
    if jax.tree.structure(params) != want:
        raise ValueError("the reference's weights do not have the "
                         "system's parameter layout")
    bad = [(a.shape, a.dtype, b.shape, b.dtype) for a, b in
           zip(jax.tree.leaves(params), jax.tree.leaves(like))
           if a.shape != b.shape or a.dtype != b.dtype]
    if bad:
        raise ValueError(f"weights differ from the system's layout: {bad[:3]}")
    return jax.block_until_ready(params)


def warm_plan(prompt_lens, width, max_batch: int, seq_axis: bool) -> dict:
    """{bucket: (prompt length, wave sizes to warm)} for the window's
    prompts. ``width`` maps a prompt length to its prefill bucket.

    The rows a wave admits shape its scatter into the pool, and where the
    state has a sequence axis so does the bucket: every (rows, bucket)
    compiles apart. The first bucket warms every wave size (the programs
    that depend on rows alone); so does every bucket that holds at least
    FULL_SHARE of the prompts. A rarer bucket heads few waves, and a wave
    grows past RARE_ROWS only when that many requests queue behind such a
    head: its larger waves are left cold (the run counts compilations in
    the window). Without a sequence axis one full wave per bucket does."""
    by: dict = {}
    for n in prompt_lens:
        by.setdefault(int(width(int(n))), []).append(int(n))
    plan = {}
    for i, b in enumerate(sorted(by)):
        if i and not seq_axis:
            ks = (max_batch,)
        elif i == 0 or len(by[b]) >= FULL_SHARE * len(prompt_lens):
            ks = tuple(range(1, max_batch + 1))
        else:
            ks = tuple(range(1, min(max_batch, RARE_ROWS) + 1))
        plan[b] = (max(by[b]), ks)
    return plan


def warm_up(srv, group, prompt_lens, out_lens, vocab: int,
            clock=None) -> dict:
    """Run every shape the window can reach once, through the server
    itself: each prefill bucket the window's prompts land in, the waves
    ``warm_plan`` names at each, a decode step, and the finish-time
    gather of each output length in ``out_lens``. The gathers compile on
    worker threads while the waves run. Returns the plan."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import Request

    rng = np.random.default_rng(0)
    B = srv.max_batch
    seq_axis = any(ax.seq is not None
                   for ax in jax.tree.leaves(group.state.axes))
    plan = warm_plan(prompt_lens, group.state.prefill_width, B, seq_axis)
    out_lens = sorted({int(n) for n in out_lens})
    # a decode step first: its tokens are what the finish-time gathers
    # concatenate (one (B, 1) array per token of the request)
    srv.submit(Request(-1, rng.integers(0, vocab, int(min(prompt_lens)),
                                        dtype=np.int32),
                       max(2, out_lens[0])))
    while srv.step():
        pass
    tok = group.last

    def gather(n):
        with srv.mesh:
            np.asarray(jnp.concatenate([tok] * n, axis=1))

    pool = ThreadPoolExecutor(GATHER_THREADS)
    gathers = [pool.submit(gather, n) for n in out_lens]
    for b, (plen, ks) in plan.items():
        t_b, c_b = time.perf_counter(), clock.n if clock else 0
        for k in ks:
            for _ in range(k):
                srv.submit(Request(-1, rng.integers(0, vocab, plen,
                                                    dtype=np.int32), 1))
            srv.step()
            while srv.step():
                pass
        log(f"warm-up bucket {b}: waves of up to {max(ks)} rows, "
            f"{time.perf_counter() - t_b:.3f} s, "
            f"{(clock.n - c_b) if clock else 0} compilations")
    t_g = time.perf_counter()
    for f in gathers:
        f.result()
    pool.shutdown()
    log(f"warm-up gathers of {len(out_lens)} output lengths: "
        f"{time.perf_counter() - t_g:.3f} s after the waves")
    jax.block_until_ready(group.last)
    return plan


def open_loop(srv, group, sched, seconds: float, trace_dir=None):
    """Submit each request when it is due, step the server, and drain.

    Returns (requests, window start, submit lateness per request,
    decode-step log of the traced stretch)."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.launch.serve import Request

    reqs = [Request(i, sched.prompts[i], int(sched.max_new[i]))
            for i in range(len(sched))]
    late = np.zeros(len(reqs))
    steps: list = []
    t_on = seconds / 3.0
    t_off = min(2.0 * seconds / 3.0, t_on + TRACE_MAX_S)
    tracing = span = None      # None: not yet, True: on, False: done
    state = group.state
    real_step = state.step

    def logged_step(last, live):
        steps.append([int(group.lens[j]) + 1 for j in range(srv.max_batch)
                      if group.reqs[j] is not None])
        return real_step(last, live)

    def start():
        nonlocal span
        jax.block_until_ready(group.last)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = TraceAnnotation("bench.traced")
        span.__enter__()
        state.step = logged_step

    def stop():
        jax.block_until_ready(group.last)
        state.step = real_step
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    i, n = 0, len(reqs)
    t0 = time.perf_counter()
    with srv.mesh:
        while True:
            now = time.perf_counter() - t0
            if trace_dir is not None and tracing is None and now >= t_on:
                start()
                tracing = True
            if tracing and now >= t_off:
                stop()
                tracing = False
            now = time.perf_counter() - t0
            while i < n and sched.due[i] <= now:
                with TraceAnnotation("server.submit"):
                    srv.submit(reqs[i])
                late[i] = now - sched.due[i]
                i += 1
            if not group.busy:
                if i >= n:
                    break
                wake = sched.due[i]
                if trace_dir is not None and tracing is not False:
                    wake = min(wake, t_off if tracing else t_on)
                with TraceAnnotation("gen.sleep"):
                    time.sleep(max(0.0, wake - (time.perf_counter() - t0)))
                continue
            if now > seconds + DRAIN_LIMIT_S:
                break
            with TraceAnnotation("server.step"):
                srv.step()
    if tracing:
        stop()
    return reqs, t0, late, steps


def wave_rows(reqs, width) -> list:
    """(bucket, rows) of each admission wave of the window: the rows of a
    wave share its first-token stamp."""
    by: dict = {}
    for r in reqs:
        if r.t_first:
            by.setdefault(r.t_first, []).append(len(r.prompt))
    return [(int(width(max(v))), len(v)) for v in by.values()]


def end_to_end(reqs, t0, sched, limits: dict, setup_s: float) -> dict:
    """The cell's end-to-end metrics, every latency from the request's due
    time; plus the per-request samples (keys starting with ``_``)."""
    done = [r for r in reqs if r.finish_reason == "max_new"
            and len(r.out) == r.max_new]
    ttft = [1e3 * (r.t_first - t0 - sched.due[r.rid]) for r in done]
    tpot = [1e3 * (r.t_done - r.t_first) / (len(r.out) - 1)
            for r in done if len(r.out) > 1]
    ok = sum(1 for r in done
             if 1e3 * (r.t_first - t0 - sched.due[r.rid]) <= limits["ttft_ms"]
             and (len(r.out) < 2 or 1e3 * (r.t_done - r.t_first)
                  / (len(r.out) - 1) <= limits["tpot_ms"]))
    # with nothing finished, latencies read as the longest wait there was
    worst = 1e3 * (max(sched.due) + DRAIN_LIMIT_S)
    last = max((r.t_done for r in done), default=t0)
    ntok = sum(len(r.out) for r in done)
    return {
        "ttft_p95_ms": pct(ttft, 95) if ttft else worst,
        "tpot_p95_ms": pct(tpot, 95) if tpot else worst,
        "output_tok_s": ntok / (last - t0) if last > t0 else 0.0,
        "slo_attain": ok / len(reqs),
        "setup_s": setup_s,
        "_ttft": ttft, "_tpot": tpot, "_done": done,
    }


class Cell:
    """A cell's files: its own, its configuration's and its mix's."""

    def __init__(self, name: str, reg=None, bench=None):
        self.reg = reg or Registry()
        self.bench = bench if bench is not None else load_benchmark()
        self.name = name
        self.cell = self.reg.cell(name)
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"{name!r} is not a workload of BENCHMARK.json")
        if (entry["config"], entry["traffic"]) != (self.cell["config"],
                                                   self.cell["traffic"]):
            raise ValueError(f"{name}: BENCHMARK.json and the cell file "
                             f"name different configs or mixes")
        self.chips = int(entry.get("chips", 1))
        self.conf = self.reg.config(self.cell["config"])
        self.mix = self.reg.traffic(self.cell["traffic"])
        self.max_batch = int(self.conf["max_batch"])
        self.max_seq = int(self.conf["max_seq"])


def devices(cell: Cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoDevice(f"JAX finds {len(devs)} {devs[0].platform} "
                       f"device(s); the cell needs {cell.chips} TPU chip(s)")
    return devs


class Served:
    """The system under test for one seed: the cell's configuration with
    the seed's weights behind one ``Server`` with one policy pool."""

    def __init__(self, cell: Cell, seed: int, *, cfg=None):
        """``cfg`` replaces the configuration's own (tests run reduced
        sizes); without it, the system's config must match the file."""
        import jax
        from repro.configs import get_config
        from repro.launch.serve import Server
        from repro.models import api
        from repro.runtime import resolve_policy

        self.cell = cell
        conf = cell.conf
        self.cfg = cfg or get_config(conf["arch"])
        like = jax.eval_shape(
            lambda: api.init_params(self.cfg, jax.random.PRNGKey(0)))
        self.spec = _model_spec(self.cfg, conf, like, check=cfg is None)
        self.ref = cell.reg.reference(conf["reference"])
        self.params = make_params(self.ref, self.spec, seed, like)
        policy = resolve_policy(self.cfg, env={}, **conf["policy"])
        self.srv = Server(self.cfg, self.params, max_batch=cell.max_batch,
                          max_seq=cell.max_seq, policy=policy)
        self.group = self.srv._groups["default"]

    def warm(self, rates, seconds: float, clock=None) -> dict:
        """Warm the shapes of windows of ``seconds`` at each of ``rates``."""
        lens = {w: np.concatenate([traffic.window_lengths(
            self.cell.mix, w, r, seconds) for r in rates])
            for w in ("prompt", "output")}
        with self.srv.mesh:     # the window steps under the mesh
            return warm_up(self.srv, self.group, lens["prompt"],
                           lens["output"], self.cfg.vocab, clock)

    def window(self, rate: float, seconds: float, seed: int,
               trace_dir=None) -> dict:
        """Offer the mix at ``rate`` for ``seconds`` and drain."""
        g = self.group
        sched = traffic.schedule(self.cell.mix, rate, seconds, seed,
                                 self.cfg.vocab, self.cell.max_seq)
        steps0, waves0 = g.decode_steps, len(g.admit_s)
        reqs, t0, late, steps = open_loop(self.srv, g, sched, seconds,
                                          trace_dir)
        return {"sched": sched, "reqs": reqs, "t0": t0, "late": late,
                "steps": steps, "decode_steps": g.decode_steps - steps0,
                "waves": len(g.admit_s) - waves0,
                "wall": time.perf_counter() - t0}

    def close(self) -> None:
        """Free the server and its state (the weights stay: the reference
        reads them)."""
        del self.srv, self.group
        gc.collect()


def served_tokens(done) -> list:
    return [(np.asarray(r.prompt), np.asarray(r.out, np.int32))
            for r in done]


def judge(served: Served, seqs: list, seed: int, *, control=False):
    """Logit gaps of a seeded sample of ``seqs`` [(prompt, served)] under
    the reference; returns (sample, gaps[, control gaps])."""
    import correct
    idx = correct.pick(seqs, seed)
    sample = [seqs[i] for i in idx]
    if not sample:
        return sample, np.array([np.finfo(np.float32).max])
    g = correct.gaps(served.ref, served.params, served.spec, sample)
    if not control:
        return sample, g
    return sample, g, correct.gaps(served.ref, served.params, served.spec,
                                   sample, control=True)


def verdict(cell: Cell, gap: float, unfinished: int):
    """(correct, checks): the widest logit gap within the cell's limit and
    every request due in the window finished."""
    limit = float(cell.cell["correct"]["max_logit_gap"])
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "unfinished": {"value": unfinished, "limit": 0}}
    return gap <= limit and unfinished == 0, checks


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, reg=None,
             bench=None, cfg=None) -> dict:
    """One run of ``cell_name``. Returns the result object whose JSON is
    the run's last line of output (``checks`` last)."""
    cell = Cell(cell_name, reg, bench)
    devs = devices(cell, require_tpu)
    dev = devs[0]
    import jax
    from repro.runtime import use_compile_cache

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    log(f"device {dev.device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    log(f"imports and device {time.perf_counter() - t_start:.3f} s")
    served = Served(cell, seed, cfg=cfg)
    log(f"weights and server {time.perf_counter() - t_start:.3f} s "
        f"({clock.n} compilations, {clock.s:.3f} s)")
    c0 = clock.n
    rate = float(cell.cell["rate_rps"])
    plan = served.warm([rate], seconds, clock)
    log(f"warm-up: {clock.n - c0} compilations "
        f"({clock.s:.3f} s compiling since start)")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; window opens")
    c_win, s_win = clock.n, clock.s
    w = served.window(rate, seconds, seed, trace_dir)
    n_compiles, s_compiles = clock.n - c_win, clock.s - s_win
    reqs, sched, late = w["reqs"], w["sched"], w["late"]
    e2e = end_to_end(reqs, w["t0"], sched, cell.cell["limits"], setup_s)
    done = e2e.pop("_done")
    ttft, tpot = e2e.pop("_ttft"), e2e.pop("_tpot")
    counts = {"decode_steps": w["decode_steps"], "waves": w["waves"],
              "decode_tokens": sum(len(r.out) - 1 for r in done),
              "max_batch": cell.max_batch}
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    log(f"window {seconds} s: {len(reqs)} requests due "
        f"({len(reqs) / seconds:.4f}/s), {len(done)} finished, "
        f"{w['wall']:.3f} s to the last completion or the drain limit")
    log(f"generator lateness at submit: p50 {1e3 * pct(late, 50):.3f} ms, "
        f"p95 {1e3 * pct(late, 95):.3f} ms, max {1e3 * late.max():.3f} ms")
    log(f"tail samples: ttft {len(ttft)}, tpot {len(tpot)} (beyond p95: "
        f"{sum(1 for x in ttft if x > e2e['ttft_p95_ms'])} / "
        f"{sum(1 for x in tpot if x > e2e['tpot_p95_ms'])}); ttft p50 "
        f"{pct(ttft, 50) if ttft else 0:.3f} ms, tpot p50 "
        f"{pct(tpot, 50) if tpot else 0:.3f} ms")
    log(f"not end-to-end metrics of a cell below its knee (the seed's order "
        f"and host timing swing them): slo_attain {e2e['slo_attain']:.4f} "
        f"within {cell.cell['limits']}, output_tok_s "
        f"{e2e['output_tok_s']:.3f}")
    log(f"compilations inside the window: {n_compiles} "
        f"({s_compiles:.3f} s)")
    rows = wave_rows(reqs, served.group.state.prefill_width)
    cold = sum(1 for b, k in rows if k not in plan.get(b, (0, ()))[1])
    top = {}
    for b, k in rows:
        top[b] = max(top.get(b, 0), k)
    log(f"admission waves: most rows by bucket {dict(sorted(top.items()))}, "
        f"{cold} of {len(rows)} of a size set-up did not warm")
    log(f"scheduler: {counts['decode_steps']} decode steps, "
        f"{counts['waves']} admission waves, {counts['decode_tokens']} "
        f"decode tokens; peak_bytes_in_use {peak}")

    # ---- the per-layer readings (traced run) ----
    per_layer, breakdown = {}, None
    if trace:
        import trace_reduce
        tr = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        view = RunView(cell.reg, counts, tr, w["steps"], cell.conf["model"],
                       cell.conf["model"]["family"],
                       cell.reg.peaks(dev.device_kind))
        for m in metrics_for(cell.bench, "per_layer", cell_name):
            v = cell.reg.metric(m["name"]).read(view)
            if v is not None:
                per_layer[m["name"]] = {"value": float(v), "unit": m["unit"]}
            else:
                log(f"per-layer {m['name']}: nothing to read")
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        log(f"traced stretch {tr.window_s:.6f} s, device busy "
            f"{tr.busy_s:.6f} s, {len(w['steps'])} decode steps logged, "
            f"{tr.straddling()} program executions past its edges")
        for k, v in view.notes.items():
            log(f"{k}: {v}")

    # ---- free the server, then the reference ----
    seqs = served_tokens(done)
    unfinished = len(reqs) - len(done)
    del w, reqs, done
    served.close()
    t_ref = time.perf_counter()
    sample, gaps = judge(served, seqs, seed)
    gap = float(gaps.max())
    log(f"reference: {len(sample)} requests, "
        f"{sum(len(s) for _, s in sample)} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s")
    ok, checks = verdict(cell, gap, unfinished)

    if trace:
        metrics = per_layer
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in metrics_for(cell.bench, "end_to_end", cell_name)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
    out = {"correct": bool(ok), "attempted": len(sched),
           "failed": unfinished, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
