"""Runner for ``"kind": "serve"`` traffic: a GPT-2 behind ``InferenceServer``
+ ``DecodeEngine`` over the wire protocol, loaded in a closed or an open
loop by the general generator (``harness/traffic.py``).

One process: the engine's thread, the server's accept and connection
threads, and the clients, all on one ``time.perf_counter``. The benchmark
reads the program's counters at the window's edges, and each request's
``RequestTrace`` marks by keeping the ``GenerateRequest`` that
``DecodeEngine.submit`` returns (a wrapper in this file, around the call
into the scheduler layer).
"""
from __future__ import annotations

import gc
import json
import queue
import threading
import time

import numpy as np

from harness import (check, device, selection, traffic as T, weights as W,
                     window)


def _build_engine(cfg, seed):
    """Weights rounded to the served type, the program's model holding
    them, and the engine over it."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    sv = cfg["serve"]
    vocab_rows = cfg["assumed"]["vocab_rows"]
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[sv["precision"]]
    picked = None
    if sv.get("flags"):
        # the configuration pins what the program would otherwise choose by
        # timing candidates at start-up (a choice that differs between
        # runs): let it choose once, keep what it chose, then pin
        from paddle_tpu.framework.flags import set_flags
        picked = selection.probe(cfg, dtype)
        print(json.dumps(dict(picked, note="kernel_selection")), flush=True)
        set_flags(dict(sv["flags"]))
    w = W.make(cfg, vocab_rows, seed,
               round_to=None if sv["precision"] == "f32" else dtype)
    named = W.program_names(w)
    del w
    paddle.seed(int(seed) % (2 ** 31))
    model = GPTForCausalLM(GPTConfig(
        vocab_size=vocab_rows, hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"],
        max_position_embeddings=cfg["n_positions"], hidden_dropout=0.0,
        attention_dropout=0.0, recompute=False))
    for name, t in model.state_dict().items():
        t._write(named[name].astype(dtype))
    del named
    model.eval()
    eng = DecodeEngine(model, EngineConfig(
        page_size=sv["page_size"], max_slots=sv["max_slots"],
        max_seq_len=sv["max_seq_len"], num_pages=sv["num_pages"],
        prefill_chunk_tokens=sv["prefill_chunk_tokens"],
        prefix_cache=sv["prefix_cache"], inflight=sv["inflight"]))
    return model, eng, picked


def _warm_lengths(table):
    """Every prompt length of the table (a sharing prompt's first sight is
    a miss of its whole length) and the tail lengths of prefix hits: what
    ``DecodeEngine.warmup`` needs to compile this traffic's programs and no
    others."""
    prompts = sorted({r["prompt"] for r in table})
    tails = sorted({r["prompt"] - r["prefix"] for r in table if r["prefix"]})
    return prompts, tails


class _Clients:
    """Sends requests over the wire and keeps what the client saw."""

    def __init__(self, port, secret, tracer):
        self.port, self.secret, self.tracer = port, secret, tracer
        self.seen = []                 # dicts, appended from any thread
        self.errors = []

    def connect(self):
        from paddle_tpu.inference.serve import RemotePredictor
        return RemotePredictor(port=self.port, secret=self.secret,
                               timeout=600.0)

    def send(self, cli, req, answer=None, t_due=None):
        n = int(req["answer"] if answer is None else answer)
        rec = {"index": req["index"], "cls": req["cls"], "t_due": t_due,
               "prompt_len": len(req["prompt_ids"]), "asked": n,
               "key": req["prompt_ids"].tobytes(), "out": None}
        rec["t_send"] = time.perf_counter()
        try:
            with self.tracer.span("bench.client.generate"):
                rec["out"] = np.asarray(cli.generate(
                    req["prompt_ids"], max_new_tokens=n))
        except Exception as e:  # noqa: BLE001 — counted as a failed request
            rec["client_error"] = f"{type(e).__name__}: {e}"
            self.errors.append(rec["client_error"])
        rec["t_recv"] = time.perf_counter()
        self.seen.append(rec)
        return rec


def _closed_loop(clients, stream, n_clients, stop, ramp_answer):
    """``n_clients`` threads; each sends its next request of ``stream`` (a
    generator without end) when the last returned. A client's FIRST request
    has its answer cut to the fraction (c + 1) / n of ``ramp_answer`` (the
    table's median answer), so that finishes spread evenly over a typical
    request's life and the ramp lasts one median request, not the longest.
    Returns (threads, events set once each client finished one)."""
    first = [next(stream) for _ in range(n_clients)]
    lock = threading.Lock()
    first_done = [threading.Event() for _ in range(n_clients)]

    def take():
        with lock:
            return next(stream)

    def client(c):
        cli = clients.connect()
        try:
            r = first[c]
            cut = min(r["answer"],
                      max(2, -(-ramp_answer * (c + 1) // n_clients)))
            clients.send(cli, r, answer=cut)
            first_done[c].set()
            while not stop.is_set():
                clients.send(cli, take())
        finally:
            first_done[c].set()
            cli.close()

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    return threads, first_done


def _open_loop(clients, reqs, due, t0, workers, stop):
    """A scheduler thread hands each request to a pool of ``workers`` wire
    clients at ``t0 + due[i]``; it stops handing out at ``stop``."""
    q = queue.Queue()

    def worker():
        cli = clients.connect()
        try:
            while True:
                item = q.get()
                if item is None:
                    return
                clients.send(cli, item[0], t_due=item[1])
        finally:
            cli.close()

    pool = [threading.Thread(target=worker, daemon=True)
            for _ in range(workers)]
    for t in pool:
        t.start()

    def schedule():
        for r, d in zip(reqs, due):
            wait = t0 + d - time.perf_counter()
            if wait > 0 and stop.wait(wait):
                break
            if stop.is_set():
                break
            q.put((r, t0 + d))
        for _ in pool:
            q.put(None)

    sched = threading.Thread(target=schedule, daemon=True)
    sched.start()
    return [sched] + pool


def run(ctx):
    from paddle_tpu.inference.serve import InferenceServer
    from paddle_tpu.observability import metrics

    cell, seed, seconds = ctx["cell"], ctx["seed"], ctx["seconds"]
    cfg, traffic = cell["config"], cell["traffic"]
    sv = cfg["serve"]
    devices = ctx["devices"]
    tracer = ctx["tracer"]

    model, eng, picked = _build_engine(cfg, seed)
    table = T.request_table(traffic)
    one_shot, tails = _warm_lengths(table)
    eng.warmup(prompt_lens=one_shot, tail_lens=tails)
    from paddle_tpu.kernels import registry
    print(json.dumps({"note": "programs",
                      "compiled": sorted(map(str, eng._programs)),
                      "kernel_winners": {str(k): v[0] for k, v in
                                         registry.table().items()}}),
          flush=True)

    # what each program reserves while it runs, by the compiler; the one
    # that certainly ran all through the window is the decode step's
    temps = {str(k): int(exe.memory_analysis().temp_size_in_bytes)
             for k, exe in eng._programs.items()}
    temp_of = next(k for k in temps if "decode" in k)
    print(json.dumps({"note": "program_temp_bytes", "programs": temps}),
          flush=True)

    captured = []
    submit = eng.submit

    def submit_and_keep(*a, **k):
        req = submit(*a, **k)
        captured.append(req)
        return req

    eng.submit = submit_and_keep
    srv = InferenceServer(None, engine=eng, auth_name="benchmark")
    server = threading.Thread(target=srv.serve_forever, daemon=True)
    server.start()
    clients = _Clients(srv.port, "benchmark", tracer)
    stop = threading.Event()
    loop = traffic["loop"]
    ramp_s = float(traffic.get("ramp_s", 0.0))
    try:
        if loop == "closed":
            n_clients = int(traffic["clients"])
            answers = sorted(r["answer"] for r in table)
            threads, first_done = _closed_loop(
                clients, T.request_stream(traffic, seed, cfg["vocab_size"]),
                n_clients, stop, answers[len(answers) // 2])
            for ev in first_done:
                ev.wait()
        else:
            sched = traffic["schedule"]
            due = T.due_times(sched, ramp_s + seconds)
            n_pre = int(traffic.get("n_prefixes", 0))
            reqs = T.requests(traffic, seed, len(due) + n_pre,
                              cfg["vocab_size"])
            # each shared prefix is sent once before the schedule starts, so
            # that the window sees the cache as a long-running server has it
            cli = clients.connect()
            sent, k = set(), 0
            for r in reqs:
                if r["prefix_id"] >= 0 and r["prefix_id"] not in sent:
                    sent.add(r["prefix_id"])
                    clients.send(cli, dict(r, index=-1 - k), answer=2)
                    k += 1
                if len(sent) == n_pre:
                    break
            cli.close()
            t0 = time.perf_counter() + 0.05
            threads = _open_loop(clients, reqs, due, t0,
                                 int(traffic["max_in_flight"]), stop)
            time.sleep(max(0.0, t0 + ramp_s - time.perf_counter()))

        pages_in_use = metrics.gauge("engine.pages_in_use")
        pool_fill = []
        snap0 = metrics.snapshot()["counters"]
        t_open = time.perf_counter()
        tracer.window_opened(t_open)
        while True:
            now = time.perf_counter()
            tracer.poll(now)
            if now >= t_open + seconds:
                break
            pool_fill.append(pages_in_use.value / (sv["num_pages"] - 1))
            time.sleep(min(0.02, t_open + seconds - now))
        t_close = time.perf_counter()
        snap1 = metrics.snapshot()["counters"]
        tracer.window_closed(t_close)
        stop.set()
        for t in threads:               # in-flight requests drain here,
            t.join(timeout=300)         # outside the window
        alive = [t for t in threads if t.is_alive()]
    finally:
        stop.set()
        try:
            cli = clients.connect()
            cli.shutdown_server()
            cli.close()
        except Exception as e:  # noqa: BLE001 — reported below
            clients.errors.append(f"shutdown: {type(e).__name__}: {e}")
        server.join(timeout=60)
        if srv._engine_thread is not None:
            srv._engine_thread.join(timeout=60)
    mem = device.memory_reading(devices)

    # join what the client saw with the program's marks
    by_key = {}
    for g in captured:
        by_key.setdefault(g.prompt.tobytes(), []).append(g)
    records = []
    for rec in clients.seen:
        got = by_key.get(rec["key"], [])
        g = got.pop(0) if got else None
        row = {k: rec[k] for k in ("index", "cls", "t_due", "t_send",
                                   "t_recv", "prompt_len", "asked")}
        row["error"] = rec.get("client_error")
        if g is not None:
            tr = g.trace
            row.update(t_accept=tr.t_accept, t_submit=tr.t_submit,
                       t_admit=tr.t_admit, t_first_token=tr.t_first_token,
                       t_done=tr.t_done, n_tokens=tr.n_tokens,
                       error=row["error"] or tr.error,
                       prefill_saved=g.u_prefill_saved,
                       prefill_computed=g.u_prefill_computed)
        row["ok_shape"] = rec["out"] is not None and \
            len(rec["out"]) == rec["prompt_len"] + rec["asked"] and \
            rec["out"][:rec["prompt_len"]].tobytes() == rec["key"]
        row["out"] = rec["out"]
        records.append(row)

    e2e = {"out_tokens_per_s": window.rate(snap0, snap1, "engine.tokens",
                                           t_open, t_close)}
    start_key = "t_due" if loop == "open" else "t_accept"
    ttft = window.ttft_samples(records, t_open, t_close, start_key)
    tpot = window.tpot_samples(records, t_open, t_close)
    if tpot:
        e2e["tpot_p50_ms"] = 1e3 * window.percentile(tpot, 50)
    # what a caller sees over this wire, which does not stream: from the
    # due time (open loop) or the send (closed loop) to the whole reply
    lat = window.latency_samples(records, t_open, t_close,
                                 "t_due" if loop == "open" else "t_send")
    if lat:
        e2e["request_latency_p50_ms"] = 1e3 * window.percentile(lat, 50)
        e2e["request_latency_mean_ms"] = 1e3 * sum(lat) / len(lat)
    finished = [r for r in records
                if window.inside(r.get("t_done"), t_open, t_close)]
    print(f'{{"note": "samples", "ttft": {len(ttft)}, "tpot": {len(tpot)}, '
          f'"latency": {len(lat)}, "finished_in_window": {len(finished)}, '
          f'"sent": {len(records)}, "window_s": {t_close - t_open:.4f}}}',
          flush=True)

    failed = sum(1 for r in records if r["error"] or not r["ok_shape"])
    failed += len(alive)
    steps = _engine_steps(metrics, t_open, t_close)
    print(json.dumps(_stalls(steps, t_open)), flush=True)

    # free the program before the reference runs
    del model, eng, srv, captured, by_key
    gc.collect()
    checks = check.Checks(cfg["limits"])
    for e in clients.errors[:5]:
        checks.fail("client", e)
    checks.add("malformed_answers", float(failed))
    t_ref = time.perf_counter()
    sample = _sample(finished, seed, int(cfg["limits_meta"]["check_requests"]))
    if not sample:
        checks.fail("served_token_gap", "no request finished in the window")
    else:
        ref = _reference_gaps(cfg, seed, sample, ctx.get("control"))
        checks.add("served_token_gap", ref["gap"], note=ref["note"])
        if "control_gap" in ref:
            print(f'{{"control": "served_token_gap", "value": '
                  f'{ref["control_gap"]}, "precision": "{ref["control"]}"}}',
                  flush=True)
            ctx.setdefault("control_out", {})["served_token_gap"] = \
                ref["control_gap"]
    print(f'{{"note": "reference", "seconds": '
          f'{time.perf_counter() - t_ref:.3f}, "requests": {len(sample)}}}',
          flush=True)
    for r in records:
        r.pop("out", None)

    return {
        "e2e": e2e, "t_open": t_open, "t_close": t_close,
        "counters_open": snap0, "counters_close": snap1,
        "records": records, "engine_steps": [d for _, d in steps],
        "loop": loop,
        "attempted": len(records), "failed": failed, "checks": checks,
        "memory": mem, "max_slots": sv["max_slots"],
        "program_temp_bytes": temps[temp_of], "program_temp_of": temp_of,
        "chips": len(devices), "pool_fill": pool_fill,
        "kernel_select_s": picked and [picked["seconds"]],
    }


def _engine_steps(metrics, t_open, t_close):
    """(start, seconds) of the program's own ``engine.step`` spans that
    began inside the window, in order, from its span ring."""
    from paddle_tpu.observability import _EPOCH
    out = []
    with metrics._span_lock:
        spans = list(metrics._spans)
    for name, _cat, ts_us, dur_us, *_ in spans:
        if name == "engine.step":
            t = _EPOCH + ts_us * 1e-6
            if t_open <= t < t_close:
                out.append((t, dur_us * 1e-6))
    return sorted(out)


def _stalls(steps, t_open):
    """Where a stall in the window would show: the longest step and the
    longest time between two steps (no work, or the engine's thread held
    up), each with its offset into the window. A run whose rate reads far
    off is told from these."""
    if not steps:
        return {"note": "steps", "count": 0}
    t, d = max(steps, key=lambda s: s[1])
    gaps = [(b[0] - a[0] - a[1], a[0] + a[1])
            for a, b in zip(steps, steps[1:])] or [(0.0, t_open)]
    g, tg = max(gaps)
    return {"note": "steps", "count": len(steps),
            "longest_ms": 1e3 * d, "longest_at_s": t - t_open,
            "longest_gap_ms": 1e3 * g, "longest_gap_at_s": tg - t_open}


def _sample(finished, seed, k):
    """``k`` of the requests that finished in the window, drawn from the
    seed, the longest (prompt + answer) always among them."""
    ok = [r for r in finished if r.get("out") is not None and r["ok_shape"]]
    if not ok:
        return []
    rng = np.random.RandomState((int(seed) + 77) % (2 ** 32))
    longest = max(ok, key=lambda r: len(r["out"]))
    rest = [r for r in ok if r is not longest]
    pick = list(rng.choice(len(rest), size=min(k - 1, len(rest)),
                           replace=False)) if rest else []
    return [longest] + [rest[i] for i in pick]


def _reference_gaps(cfg, seed, sample, control=None):
    """The plain reference once over each sampled prompt with its served
    tokens: the widest gap by which a served token's logit lies below the
    reference's best at that position, as a share of the largest |logit|
    compared. With ``control`` (a precision) also the same reading for the
    token that the lower precision puts first."""
    import sys
    from pathlib import Path
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from reference import gpt2
    sv = cfg["serve"]
    dtype = None if sv["precision"] == "f32" else \
        {"bf16": jnp.bfloat16}[sv["precision"]]
    w = W.make(cfg, cfg["assumed"]["vocab_rows"], seed, round_to=dtype)
    lmax = max(len(r["out"]) for r in sample)
    lmax = -(-lmax // 128) * 128
    ids = np.zeros((len(sample), lmax), np.int32)
    for i, r in enumerate(sample):
        ids[i, :len(r["out"])] = r["out"]        # causal: the tail is inert

    def gaps_of(logits_row, toks, n0):
        t = np.arange(n0, len(toks))
        row = logits_row[t - 1]                  # position t-1 predicts t
        return row.max(-1) - row[np.arange(len(t)), toks[t]], \
            float(np.abs(row).max())

    out = {}
    with jax.enable_x64(False):
        fwd = jax.jit(gpt2.logits, static_argnums=(2, 3))
        worst, top, where, n_tok = 0.0, 0.0, "", 0
        ctl_worst = 0.0
        # one request at a time: [1, L, V] float32 logits are 100-200 MB
        for i, r in enumerate(sample):
            lg = np.asarray(fwd(w, jnp.asarray(ids[i:i + 1]), cfg["n_head"],
                                "f32"))[0]
            toks = np.asarray(r["out"])
            g, t = gaps_of(lg, toks, r["prompt_len"])
            n_tok += len(g)
            top = max(top, t)
            if not g.max() <= worst:
                worst = float(g.max())
                where = f"request {r['index']} +{int(g.argmax())}"
            if control:
                lc = np.asarray(fwd(w, jnp.asarray(ids[i:i + 1]),
                                    cfg["n_head"], control))[0]
                t_pos = np.arange(r["prompt_len"], len(toks)) - 1
                first = lc[t_pos].argmax(-1)
                gc_ = lg[t_pos].max(-1) - lg[t_pos, first]
                ctl_worst = max(ctl_worst, float(gc_.max()))
    out["gap"] = worst / top
    out["note"] = f"{where}; {n_tok} tokens of {len(sample)} requests; " \
                  f"max |logit| {top:.4f}"
    if control:
        out["control"] = control
        out["control_gap"] = ctl_worst / top
    return out
