"""Start-up on the span ring (docs/OBSERVABILITY.md "Reading a start-up").

What must hold:
- JAX's own compile events (`jax.monitoring`) become `xla.trace`,
  `xla.lower` and `xla.compile` spans under the span open on the thread
  that compiled, and seconds on the `xla.*_seconds` counters; an event
  under a millisecond reaches its counter only; `xla.compile` says what
  the persistent cache did (`hit`, `miss`, `off`); the listeners register
  once however often `listen()` is called;
- the engine's start-up phases have spans (`engine.init` over
  `engine.load_params` and `engine.cache_alloc`, `engine.warmup` over its
  `engine.compile:*`) and a compiled program's first launch says
  `first=true`, once;
- `to_static`'s first call of a signature is `jit.first_dispatch:<fn>`,
  where XLA's compile lands; no later call has a span;
- `import paddle_tpu` is `package.import`.
"""
import collections
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import compile_cache
from paddle_tpu.observability import MetricsRegistry, metrics

ROOT = Path(__file__).resolve().parents[1]
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
SECONDS = ("xla.trace_seconds", "xla.lower_seconds", "xla.compile_seconds")


def _counters(*names):
    return {n: metrics.counter(n).value for n in names}


def _by_parent(spans):
    out = collections.defaultdict(list)
    for s in spans:
        out[s.parent].append(s)
    return out


@pytest.fixture(autouse=True, scope="module")
def cache_as_a_fresh_process_has_it():
    """An entry point's ``main`` run inside the worker by an earlier file
    (``router.main`` in tests/test_router.py) leaves JAX's persistent
    cache on for the rest of the process; these tests read ``cache: off``
    of one in which nothing has enabled it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


@pytest.fixture
def every_event(monkeypatch):
    """A compile on the CPU at a test's size can take under a millisecond:
    let every event reach the ring."""
    monkeypatch.setattr(compile_cache, "MIN_SPAN_SECONDS", 0.0)


# ------------------------------------------------------------ the primitive


def test_open_span_is_the_innermost_on_this_thread():
    reg = MetricsRegistry()
    assert reg.open_span() is None
    seen = {}
    with reg.span("outer") as outer:
        assert reg.open_span() is outer
        with reg.span("inner") as inner:
            assert reg.open_span() is inner
            t = threading.Thread(
                target=lambda: seen.update(other=reg.open_span()))
            t.start()
            t.join(timeout=30)
        assert reg.open_span() is outer
    assert reg.open_span() is None and seen == {"other": None}


# ------------------------------------------------------- JAX's compile events


def test_a_fresh_jit_writes_trace_lower_and_compile_under_the_open_span(
        every_event):
    def fresh(x):
        for i in range(40):
            x = jnp.sin(x) * (i + 1.5)
        return x.sum()

    before = _counters(*SECONDS)
    t0 = time.perf_counter()
    with metrics.span("test.cause") as cause:
        jax.block_until_ready(jax.jit(fresh)(jnp.ones((8, 8))))
    after = _counters(*SECONDS)
    mine = [s for s in metrics.spans(prefix="xla.", since=t0)
            if "fresh" in s.args.get("fun_name", "")]
    assert sorted(s.name for s in mine) == \
        ["xla.compile", "xla.lower", "xla.trace"]
    for s in mine:
        assert s.parent == cause.id and s.cat == "compile"
        assert s.tid == threading.get_ident()
        assert cause.t0 <= s.t0 and s.t0 + s.dur <= cause.t0 + cause.dur
    order = sorted(mine, key=lambda s: s.t0)
    assert [s.name for s in order] == ["xla.trace", "xla.lower",
                                       "xla.compile"]
    compiled = order[-1]
    assert compiled.args == {"cache": "off", "fun_name": "jit(fresh)"}
    assert "cache" not in order[0].args
    for name, phase in zip(SECONDS, order):
        assert after[name] - before[name] >= phase.dur > 0


def test_a_compile_outside_any_span_is_a_root(every_event):
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(lambda x: x * 3.25 + 1)(jnp.ones(3)))
    got = metrics.spans(name="xla.compile", since=t0)
    assert got and all(s.parent is None for s in got)


def test_an_event_under_a_millisecond_reaches_its_counter_only():
    from jax import monitoring
    before = _counters(*SECONDS)
    t0 = time.perf_counter()
    for event in (TRACE, LOWER, COMPILE):
        monitoring.record_event_duration_secs(event, 0.0004, fun_name="tiny")
    after = _counters(*SECONDS)
    assert [after[n] - before[n] for n in SECONDS] == \
        pytest.approx([0.0004] * 3)
    assert metrics.spans(prefix="xla.", since=t0) == []
    # at the threshold it is a span whose start lies `duration` back
    monitoring.record_event_duration_secs(TRACE, 0.25, fun_name="long")
    now = time.perf_counter()
    (sp,) = [s for s in metrics.spans(prefix="xla.", since=t0 - 1.0)
             if s.args.get("fun_name") == "long"]
    assert sp.name == "xla.trace" and sp.args == {"fun_name": "long"}
    assert sp.dur == 0.25 and sp.t0 + sp.dur == pytest.approx(now, abs=0.05)


def test_registering_twice_records_once():
    from jax._src import monitoring
    compile_cache.listen()
    compile_cache.listen()
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1
    before = _counters("xla.lower_seconds")
    t0 = time.perf_counter() - 0.01
    monitoring.record_event_duration_secs(LOWER, 0.005, fun_name="once")
    assert _counters("xla.lower_seconds")["xla.lower_seconds"] - \
        before["xla.lower_seconds"] == pytest.approx(0.005)
    assert len(metrics.spans(name="xla.lower", since=t0)) == 1


def test_events_of_other_names_are_left_alone():
    from jax import monitoring
    before = metrics.snapshot()["counters"]
    spans = len(metrics.spans())
    monitoring.record_event("/jax/some/other/event")
    monitoring.record_event_duration_secs("/jax/some/other/duration", 0.5)
    after = metrics.snapshot()["counters"]
    assert {k: v for k, v in after.items() if k.startswith("xla.")} == \
        {k: v for k, v in before.items() if k.startswith("xla.")}
    assert len(metrics.spans()) == spans


def test_time_saved_below_zero_leaves_its_counter_where_it_was():
    """JAX reports the recorded compile time less the retrieval: negative
    where the cache was the slower way. A counter only rises."""
    from jax import monitoring
    saved = "/jax/compilation_cache/compile_time_saved_sec"
    c = metrics.counter("xla.compile_time_saved_seconds")
    v = c.value
    monitoring.record_event_duration_secs(saved, -0.5)
    assert c.value == v
    monitoring.record_event_duration_secs(saved, 0.75)
    assert c.value == pytest.approx(v + 0.75)


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache in a directory of this test's, every program
    written however quick; the process is left as it was found."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], -1)
    try:
        yield tmp_path
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()


def test_a_second_compile_of_the_program_reads_the_cache(persistent_cache,
                                                         every_event):
    def cached_once(x):
        return jnp.tanh(x * 2.75).sum() + 11.0

    def compile_span(t0):
        (sp,) = [s for s in metrics.spans(name="xla.compile", since=t0)
                 if s.args["fun_name"] == "jit(cached_once)"]
        return sp

    x = jnp.ones((4, 4))
    before = _counters("xla.cache_hits", "xla.cache_misses",
                       "xla.cache_retrieval_seconds")
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(cached_once)(x))
    cold = _counters(*before)
    assert compile_span(t0).args["cache"] == "miss"
    assert cold["xla.cache_misses"] == before["xla.cache_misses"] + 1
    assert cold["xla.cache_hits"] == before["xla.cache_hits"]
    assert any(persistent_cache.iterdir())

    jax.clear_caches()
    t1 = time.perf_counter()
    jax.block_until_ready(jax.jit(cached_once)(x))
    warm = _counters(*before)
    assert compile_span(t1).args["cache"] == "hit"
    assert warm["xla.cache_hits"] == cold["xla.cache_hits"] + 1
    assert warm["xla.cache_misses"] == cold["xla.cache_misses"]
    assert warm["xla.cache_retrieval_seconds"] > \
        cold["xla.cache_retrieval_seconds"]
    # tracing and lowering were paid again: no cache saves them
    names = {s.name for s in metrics.spans(prefix="xla.", since=t1)
             if "cached_once" in s.args.get("fun_name", "")}
    assert names == {"xla.trace", "xla.lower", "xla.compile"}


# -------------------------------------------------------- the engine's phases


def _tiny_model(seed=7, vocab=97, max_pos=64):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64,
                    max_position_embeddings=max_pos, hidden_dropout=0.0,
                    attention_dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def served():
    """One tiny engine built, warmed and run: its spans, oldest first."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    model = _tiny_model()
    t0 = time.perf_counter()
    eng = DecodeEngine(model, EngineConfig(
        page_size=4, max_slots=2, min_bucket=8, prefill_chunk_tokens=8))
    eng.warmup(prompt_lens=(5, 21))
    programs = len(eng._programs)
    rng = np.random.RandomState(3)
    # a one-shot prefill, a chunked one, and both again
    reqs = [eng.submit(rng.randint(0, 97, n).astype(np.int32), 5)
            for n in (5, 21, 6, 19)]
    eng.run_until_idle(max_steps=120)
    assert all(r.done and r._error is None for r in reqs)
    assert len(eng._programs) == programs == 3
    return eng, metrics.spans(since=t0)


def test_engine_init_lies_over_load_params_and_cache_alloc(served):
    eng, spans = served
    (init,) = [s for s in spans if s.name == "engine.init"]
    kids = {s.name: s for s in _by_parent(spans)[init.id]
            if s.name.startswith("engine.")}
    assert set(kids) == {"engine.load_params", "engine.cache_alloc"}
    assert init.parent is None and init.cat == "startup"
    leaves = len(jax.tree_util.tree_leaves(eng._params))
    assert kids["engine.load_params"].args == {"leaves": leaves}
    assert kids["engine.cache_alloc"].args == {"bytes": sum(
        int(a.nbytes) for a in jax.tree_util.tree_leaves(eng._cache))}
    assert kids["engine.load_params"].t0 < kids["engine.cache_alloc"].t0
    for k in kids.values():
        assert init.t0 <= k.t0 and k.t0 + k.dur <= init.t0 + init.dur


def test_engine_warmup_lies_over_its_compiles_and_they_over_xla(served):
    _, spans = served
    (warm,) = [s for s in spans if s.name == "engine.warmup"]
    assert warm.args == {"compiled": 3} and warm.parent is None
    kids = _by_parent(spans)
    compiles = [s for s in spans if s.name.startswith("engine.compile:")]
    assert sorted(s.name for s in compiles) == [
        "engine.compile:decode", "engine.compile:prefill",
        "engine.compile:prefill_chunk"]
    for c in compiles:
        assert c.parent == warm.id
        # (the kernel registry resolves its arms while the program is traced)
        inside = {s.name for s in kids[c.id]
                  if not s.name.startswith("kernel.select:")}
        assert inside == {"xla.trace", "xla.lower", "xla.compile"}
        (xc,) = [s for s in kids[c.id] if s.name == "xla.compile"]
        assert xc.args == {"cache": "off", "fun_name": "jit(program)"}
        # JAX's three phases account for the program's compile span
        covered = sum(s.dur for s in kids[c.id]
                      if s.args.get("fun_name") in ("program",
                                                    "jit(program)"))
        assert 0.5 * c.dur < covered <= c.dur


def test_first_is_said_on_exactly_one_launch_of_each_program(served):
    _, spans = served
    launches = [s for s in spans
                if s.name in ("engine.dispatch", "engine.prefill_launch")]
    firsts = [s for s in launches if s.args.get("first")]
    assert sorted((s.name, s.args.get("kind")) for s in firsts) == [
        ("engine.dispatch", None), ("engine.prefill_launch", "chunk"),
        ("engine.prefill_launch", "oneshot")]
    for f in firsts:
        assert f.args["first"] is True
        same = [s for s in launches if s.name == f.name
                and s.args.get("kind") == f.args.get("kind")]
        assert len(same) > 1 and min(same, key=lambda s: s.t0) is f
    assert all("first" not in s.args for s in launches if s not in firsts)


def test_building_the_gpt_model_is_a_span_over_its_eager_compiles(
        every_event):
    t0 = time.perf_counter()
    _tiny_model(seed=11, vocab=89, max_pos=48)     # shapes nobody drew yet
    got = metrics.spans(since=t0)
    (build,) = [s for s in got if s.name == "model.init:GPTForCausalLM"]
    assert build.args == {"layers": 2} and build.cat == "startup"
    assert build.parent is None
    drawn = [s for s in got if s.name == "xla.compile"]
    assert drawn and all(s.parent == build.id for s in drawn)


def test_a_refresh_of_the_weights_is_a_load_params_span_of_its_own():
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    model = _tiny_model(seed=9)
    eng = DecodeEngine(model, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
    t0 = time.perf_counter()
    eng.refresh_params(model)
    (sp,) = metrics.spans(name="engine.load_params", since=t0)
    assert sp.parent is None and sp.args["leaves"] > 0
    assert metrics.gauge("engine.param_leaves").value == sp.args["leaves"]


# ---------------------------------------------------------- the captured step


def test_to_static_spans_the_first_dispatch_of_a_signature_only(every_event):
    @paddle.jit.to_static
    def doubled(x):
        return (x * 2.0).sum()

    x = paddle.randn([4, 4])
    t0 = time.perf_counter()
    doubled(x)
    first = metrics.spans(since=t0)
    t1 = time.perf_counter()
    doubled(x)
    doubled(x)
    assert metrics.spans(since=t1) == []

    by_name = {s.name: s for s in first if s.name.startswith("jit.")}
    assert set(by_name) == {"jit.capture:doubled",
                            "jit.first_dispatch:doubled"}
    cap, disp = by_name["jit.capture:doubled"], \
        by_name["jit.first_dispatch:doubled"]
    assert cap.parent is None and disp.parent is None
    assert cap.t0 + cap.dur <= disp.t0 and disp.cat == "compile"
    under = {s.name for s in first if s.parent == disp.id}
    assert under == {"xla.trace", "xla.lower", "xla.compile"}
    (xc,) = [s for s in first if s.name == "xla.compile"
             and s.parent == disp.id]
    assert xc.args["fun_name"] == "jit(pure)"
    # the probe is traced under the capture, and compiles nothing
    probes = [s for s in first if s.parent == cap.id]
    assert {s.name for s in probes} == {"xla.trace"}

    # another signature is another program: one more first dispatch
    t2 = time.perf_counter()
    doubled(paddle.randn([2, 8]))
    doubled(paddle.randn([2, 8]))
    again = [s.name for s in metrics.spans(prefix="jit.", since=t2)]
    assert again == ["jit.capture:doubled", "jit.first_dispatch:doubled"]


def test_multi_steps_spans_its_own_first_dispatch(every_event):
    import paddle_tpu.nn as nn
    model = nn.Linear(4, 1)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    @paddle.jit.to_static
    def tiny_step(x):
        loss = model(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    k_steps = tiny_step.multi_steps(3)
    xs = paddle.randn([3, 2, 4])
    t0 = time.perf_counter()
    k_steps(xs)
    names = [s.name for s in metrics.spans(prefix="jit.", since=t0)]
    assert names == ["jit.capture:tiny_step", "jit.first_dispatch:tiny_step"]
    t1 = time.perf_counter()
    k_steps(xs)
    assert metrics.spans(since=t1) == []


def test_scan_train_step_no_longer_calls_a_first_step_a_compile():
    """`train.compile` timed a whole first step by hand; XLA's own compile
    is on the ring now, and `train.compile_count` still counts programs."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.train import ScanTrainStep
    paddle.seed(5)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
        intermediate_size=32, max_position_embeddings=16,
        hidden_dropout=0.0, attention_dropout=0.0))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = ScanTrainStep(model, opt)
    ids = np.random.RandomState(0).randint(0, 64, (2, 9))
    compiles = metrics.counter("train.compile_count").value
    t0 = time.perf_counter()
    step(ids[:, :-1], ids[:, 1:])
    got = metrics.spans(since=t0)
    assert metrics.counter("train.compile_count").value == compiles + 1
    assert not [s for s in got if s.name == "train.compile"]
    assert [s for s in got if s.name == "xla.compile"
            and s.dur >= compile_cache.MIN_SPAN_SECONDS]
    assert "train.compile_ms" not in metrics.snapshot()["gauges"]


# ---------------------------------------------------------------- the package


@pytest.mark.parametrize("jax_first", [False, True])
def test_package_import_is_one_span_that_says_whether_jax_was_loaded(
        jax_first):
    code = (("import jax\n" if jax_first else "") +
            "import json, sys, time\n"
            "import paddle_tpu\n"
            "done = time.perf_counter()\n"
            "from paddle_tpu import observability as O\n"
            "got = O.metrics.spans(name='package.import')\n"
            "print(json.dumps({'n': len(got), 'args': got[0].args,\n"
            "    'cat': got[0].cat, 'parent': got[0].parent,\n"
            "    'from_epoch': got[0].t0 - O._EPOCH, 'dur': got[0].dur,\n"
            "    'to_done': done - (got[0].t0 + got[0].dur),\n"
            "    'others': len(O.metrics.spans()) - len(got)}))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["n"] == 1 and got["others"] == 0
    assert got["args"] == {"jax_preloaded": jax_first}
    assert got["cat"] == "startup" and got["parent"] is None
    # from the registry's epoch (the package's first statement) to its last
    assert abs(got["from_epoch"]) < 1e-6 and got["dur"] > 0.05
    assert 0 <= got["to_done"] < 0.05
