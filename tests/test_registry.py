"""Kernel selection (`paddle_tpu/kernels/registry.py`): dispatch,
viability, measured selection and its table, the
`kernel.dispatch.{op}.{impl}` counters, the version-1 winner file, and the
ast-guards: every kernel call site routes through the registry, every op
is registered by the module that implements it, and the registry imports
nothing of `paddle_tpu.kernels`."""
import ast
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import registry
from paddle_tpu.observability import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_cache():
    registry.clear()
    yield
    registry.clear()


def _flash(shape=(1, 1, 16, 8), causal=False, partitioned=False, **kw):
    """One flash signature resolved as `flash_attention_fn` resolves it."""
    key, measure = fa._selection(shape, shape, jnp.dtype("float32"), causal,
                                 None, partitioned)
    return registry.dispatch("flash_attention",
                             ctx={"partitioned": partitioned}, key=key,
                             measure=measure, **kw)


def _paged(**kw):
    key, measure = pa._paged_selection(1, 2, 2, 1, 2, "float32")
    return registry.dispatch("paged_attention", key=key, measure=measure,
                             **kw)


def _prefill(**kw):
    key, measure = pa._prefill_selection(2, 2, 2, 1, 2, "float32")
    return registry.dispatch("prefill_attention", key=key, measure=measure,
                             **kw)


MEASURED = {"flash_attention": _flash, "paged_attention": _paged,
            "prefill_attention": _prefill}


# ------------------------------------------------------------- dispatch


class TestDispatch:
    def test_unknown_op_and_unknown_impl_are_loud(self):
        with pytest.raises(KeyError, match="unknown kernel op"):
            registry.dispatch("no_such_op")
        with pytest.raises(ValueError, match="no impl"):
            registry.dispatch("paged_attention", forced="bogus")

    @pytest.mark.parametrize("gone", ["splash", "mosaic", "dense"])
    def test_forcing_a_deleted_flash_arm_is_the_loud_error(self, gone):
        from paddle_tpu.framework.flags import set_flags
        q = jnp.zeros((1, 8, 2, 4), jnp.float32)
        set_flags({"tpu_flash_impl": gone})
        try:
            with pytest.raises(ValueError, match=f"no impl '{gone}'"):
                fa.flash_attention_fn(causal=True)(q, q, q)
        finally:
            set_flags({"tpu_flash_impl": "auto"})

    def test_forced_outside_viable_set_allowed_by_default(self):
        # interpret-mode parity testing forces pallas off-TPU on purpose
        assert registry.dispatch("paged_attention", forced="pallas") \
            == "pallas"

    def test_require_viable_degrades_to_first_candidate(self):
        # the fused-CE rule: "fused" wanted but mp>1 -> dense
        from paddle_tpu.kernels import fused_ce  # noqa: F401 — registers
        assert registry.dispatch("fused_ce", forced="fused",
                                 ctx={"mp": 2}, require_viable=True) \
            == "dense"
        assert registry.dispatch("fused_ce", forced="fused",
                                 ctx={"mp": 1}, require_viable=True) \
            == "fused"

    def test_counters_count_every_resolution_plus_alias(self):
        before = metrics.counter(
            "kernel.dispatch.paged_attention.xla").value
        alias_before = metrics.counter("paged_attention.impl.xla").value
        registry.dispatch("paged_attention", forced="xla")
        assert metrics.counter(
            "kernel.dispatch.paged_attention.xla").value == before + 1
        assert metrics.counter(
            "paged_attention.impl.xla").value == alias_before + 1

    def test_sp_attention_viability(self):
        op = registry.ops()["sp_attention"]
        assert op.candidates({"heads": 8, "sp": 2}) == ["ring", "ulysses"]
        assert op.candidates({"heads": 7, "sp": 2}) == ["ring"]
        # "auto" picks the first viable candidate
        assert registry.dispatch("sp_attention", forced="auto",
                                 ctx={"heads": 7, "sp": 2}) == "ring"

    def test_prefill_parity_ctx_drops_pallas(self, monkeypatch):
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        op = registry.ops()["prefill_attention"]
        assert op.candidates({"parity": True}) == ["xla", "pallas"]
        assert op.candidates({"parity": False}) == ["xla"]

    def test_auto_prefill_selection_respects_parity_gate(self, monkeypatch):
        """The AUTO path honors the parity gate: the candidates `dispatch`
        computes are the ones `select` decides among (and the gated
        signature keys its own entry), so a narrowing-pool one-shot
        prefill can never measure-and-pick the pool-reading pallas arm,
        even on a backend where pallas wins every race."""
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        monkeypatch.setattr(
            registry, "measure",
            lambda fn, args, **kw: pytest.fail(
                "parity-gated selection must not measure"))
        assert pa.prefill_impl(8, 4, 4, 2, 8, jnp.float32,
                               parity=False) == "xla"
        # ... and the gated signature's pin lands under its OWN key, so
        # an ungated call with the same geometry still measures fresh
        gated_keys = [k for k in registry.table()
                      if k[0] == "prefill" and str(k[-1])
                      .endswith("/no-parity")]
        assert gated_keys, registry.table().keys()

    @pytest.mark.parametrize("backend, partitioned, want", [
        ("tpu", False, ["xla", "authored"]),
        ("tpu", True, ["xla"]),
        ("cpu", False, ["xla"]),
        ("cpu", True, ["xla"]),
    ])
    def test_flash_candidates(self, backend, partitioned, want,
                              monkeypatch):
        """By backend and partitioning alone: a tiled and an untiled
        length, a small and a huge logits tensor give one list."""
        cands = registry.ops()["flash_attention"].candidates
        assert cands({"backend": backend, "partitioned": partitioned}) \
            == want
        monkeypatch.setattr(registry, "backend", lambda: backend)
        seen = []
        monkeypatch.setattr(
            registry, "select",
            lambda op, key, c, measure: seen.append((key[2], list(c)))
            or c[0])
        for shape in ((1, 1, 128, 64), (1, 1, 200, 64), (17, 16, 1024, 64)):
            _flash(shape, partitioned=partitioned)
        assert seen == [(s, want) for s in (
            (1, 1, 128, 64), (1, 1, 200, 64), (17, 16, 1024, 64))]


# ---------------------------------------------------- measured selection


class TestMeasuredSelection:
    def test_off_tpu_one_candidate_is_pinned_and_nothing_measured(
            self, monkeypatch):
        monkeypatch.setattr(
            registry, "measure",
            lambda *a, **kw: pytest.fail("one candidate: nothing to time"))
        assert _flash((1, 1, 8, 4)) == "xla"
        sp = metrics.spans("kernel.select:flash_attention")[-1]
        assert sp.args == {"pick": "xla", "source": "single",
                           "timings_ms": {}}

    def test_both_arms_are_measured_for_real(self, monkeypatch):
        # on a TPU's name both arms run (the kernel in the interpreter)
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        ran = []
        real = fa._impl_call
        monkeypatch.setattr(
            fa, "_impl_call",
            lambda impl, *a: ran.append(impl) or real(impl, *a))
        assert _flash((1, 2, 8, 4)) in ("xla", "authored")
        assert set(ran) == {"xla", "authored"}
        (_, per_impl), = registry.table().values()
        assert all(isinstance(t, float) for t in per_impl.values())

    def test_measured_selection_and_cache(self, monkeypatch):
        # pretend we're on real TPU so every arm is offered
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        timings = iter([5.0, 2.0])            # in candidate order
        monkeypatch.setattr(registry, "measure",
                            lambda fn, args, **kw: next(timings))
        assert _flash((1, 1, 128, 64), causal=True) == "authored"
        # second call: memory hit, no re-measure (the iterator is spent)
        assert _flash((1, 1, 128, 64), causal=True) == "authored"
        (key, (pick, per_impl)), = registry.table().items()
        assert key[0] == "flash" and pick == "authored"
        assert per_impl == {"xla": 5.0, "authored": 2.0}

    @pytest.mark.parametrize("op", sorted(MEASURED))
    def test_candidates_computed_once_and_those_are_timed(self, op,
                                                          monkeypatch):
        """`dispatch` asks the op for its candidates ONCE, and the list
        `select` times is that list: nothing filters a second time."""
        asked = []
        monkeypatch.setattr(
            registry.ops()[op], "candidates",
            lambda ctx: asked.append(ctx) or ["xla", "second", "third"])
        timed = []

        def measure(fn, args, **kw):
            timed.append(1)
            return float(len(timed))

        monkeypatch.setattr(registry, "measure", measure)
        seen = []
        select = registry.select
        monkeypatch.setattr(
            registry, "select",
            lambda op_, key, cands, m: seen.append(list(cands))
            or select(op_, key, cands, m))
        assert MEASURED[op]() == "xla"
        assert len(asked) == 1 and len(timed) == 3
        assert seen == [["xla", "second", "third"]]
        (_, per_impl), = registry.table().values()
        assert list(per_impl) == ["xla", "second", "third"]

    def test_failing_candidate_is_recorded_and_warned(self, caplog):
        """A candidate the device refuses is not data to be dropped: its
        error lands in the table entry in place of a time and is logged
        at WARNING, so whoever reads `registry.table()` sees the refusal."""
        def measure(impl):
            if impl != "xla":
                raise RuntimeError("mosaic lowering failed")
            return 1.0

        with caplog.at_level("WARNING", logger="paddle_tpu.kernels.registry"):
            w = registry.select("flash_attention", ("flash", "t"),
                                ["xla", "second", "authored"], measure)
        assert w == "xla"
        (winner, per_impl), = registry.table().values()
        assert winner == "xla" and per_impl["xla"] == 1.0
        for impl in ("second", "authored"):
            assert per_impl[impl] == "RuntimeError: mosaic lowering failed"
        warned = [r for r in caplog.records if r.levelname == "WARNING"]
        assert len(warned) == 2 and "lowering failed" in warned[0].message

    def test_every_candidate_failing_raises(self):
        def measure(impl):
            raise RuntimeError(f"{impl} refused")

        with pytest.raises(RuntimeError, match="every candidate"):
            registry.select("flash_attention", ("flash", "t"),
                            ["xla", "authored"], measure)
        assert registry.table() == {}

    def test_auto_flag_selects_and_records_on_cpu(self):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.framework.flags import set_flags
        set_flags({"tpu_flash_impl": "auto"})
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(1, 8, 2, 4).astype(np.float32))
        out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        assert np.isfinite(np.asarray(out._data)).all()
        assert [k[0] for k in registry.table()] == ["flash"]
        sp = metrics.spans("kernel.select:flash_attention")[-1]
        assert sp.args["pick"] == registry.table().popitem()[1][0]

    def test_candidates_execute_while_a_program_is_being_traced(
            self, monkeypatch):
        """Selections run at trace time of a step program. The candidates
        must EXECUTE there (concrete arrays, a real clock), not be staged
        into the outer trace where `block_until_ready` is a no-op."""
        concrete = []

        def measure(fn, args, **kw):
            leaves = jax.tree_util.tree_leaves((args, fn(*args)))
            concrete.append(not any(isinstance(a, jax.core.Tracer)
                                    for a in leaves))
            return 1.0

        monkeypatch.setattr(registry, "measure", measure)

        monkeypatch.setattr(registry.ops()["flash_attention"], "candidates",
                            lambda ctx: ["xla", "xla"])

        @jax.jit
        def program(x):
            _flash()
            return x + 1

        program(jnp.zeros(2))
        assert concrete == [True, True]     # one per candidate

    def test_measure_times_a_call_and_shares_back_to_back_launches(self):
        calls = []

        def fn(x):
            calls.append(1)
            return x + 1

        t = registry.measure(fn, (jnp.zeros(2),), warmup=1, reps=2, calls=4)
        assert len(calls) == 1 + 2 * 4 and 0 < t < 1.0


class TestSiteCounters:
    """Each migrated dispatch site lands its own kernel.dispatch.* count."""

    def test_flash_site(self):
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(1, 8, 2, 4).astype(np.float32))
        before = sum(v for k, v in metrics.snapshot()["counters"].items()
                     if k.startswith("kernel.dispatch.flash_attention."))
        F.scaled_dot_product_attention(q, q, q, is_causal=True)
        after = sum(v for k, v in metrics.snapshot()["counters"].items()
                    if k.startswith("kernel.dispatch.flash_attention."))
        assert after > before

    def test_paged_and_prefill_sites(self):
        from paddle_tpu.kernels import paged_attention as pa
        rng = np.random.RandomState(1)
        nh, dh, ps, maxp = 2, 8, 4, 3
        kp = jnp.asarray(rng.randn(1 + maxp, ps, nh, dh).astype(np.float32))
        vp = jnp.asarray(rng.randn(1 + maxp, ps, nh, dh).astype(np.float32))
        row = jnp.asarray(np.arange(1, maxp + 1, dtype=np.int32))
        q1 = jnp.asarray(rng.randn(2, nh, dh).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [1, 2, 3]], np.int32))
        before = metrics.counter(
            "kernel.dispatch.paged_attention.xla").value
        pa.paged_attention(q1, kp, vp, pt,
                           jnp.asarray([2, 5], jnp.int32))
        assert metrics.counter(
            "kernel.dispatch.paged_attention.xla").value == before + 1
        qc = jnp.asarray(rng.randn(1, 4, nh, dh).astype(np.float32))
        pbefore = metrics.counter(
            "kernel.dispatch.prefill_attention.xla").value
        pa.prefill_attention(qc, kp, vp, row, jnp.int32(0), jnp.int32(4))
        assert metrics.counter(
            "kernel.dispatch.prefill_attention.xla").value == pbefore + 1

    def test_fused_ce_and_layernorm_sites(self):
        from paddle_tpu.models.gpt import GPTConfig, _fused_ce_impl
        before = metrics.counter("kernel.dispatch.fused_ce.fused").value
        assert _fused_ce_impl(GPTConfig()) == "fused"
        assert metrics.counter(
            "kernel.dispatch.fused_ce.fused").value == before + 1
        dbefore = metrics.counter("kernel.dispatch.fused_ce.dense").value
        assert _fused_ce_impl(GPTConfig(fused_ce=False)) == "dense"
        assert metrics.counter(
            "kernel.dispatch.fused_ce.dense").value == dbefore + 1

        from paddle_tpu.incubate.nn import FusedLayerNorm
        lbefore = metrics.counter(
            "kernel.dispatch.fused_layernorm.pallas").value
        ln = FusedLayerNorm(8)
        assert metrics.counter(
            "kernel.dispatch.fused_layernorm.pallas").value == lbefore + 1
        # forward runs EAGERLY per call: the dispatch count stays at the
        # construction-time selection, never per invocation
        for _ in range(3):
            ln(paddle.to_tensor(np.random.RandomState(2)
                                .randn(3, 8).astype(np.float32)))
        assert metrics.counter(
            "kernel.dispatch.fused_layernorm.pallas").value == lbefore + 1


# ---------------------------------------------------------- persistence


class TestWinnerFile:
    """`PADDLE_AUTOTUNE_CACHE` is the version-1 table and nothing older: a
    file written before this module lost its adapter still answers, any
    other file is ignored, never fatal."""

    def _no_measuring(self, monkeypatch, path):
        monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE", str(path))
        monkeypatch.setattr(
            registry, "measure",
            lambda *a, **kw: pytest.fail("disk winner ignored: measured"))

    @pytest.mark.parametrize("op, key, alt", [
        # the EXACT keys the adapter wrote before PR 46 — byte for byte
        ("flash_attention", ("flash", "{b}", (1, 1, 16, 8), (1, 1, 16, 8),
                             "float32", False), "alt"),
        ("paged_attention", ("paged", "{b}", 1, 2, 2, 1, 2, "float32"),
         "alt"),
        ("prefill_attention", ("prefill", "{b}", 2, 2, 2, 1, 2, "float32"),
         "alt"),
    ])
    def test_v1_file_written_by_the_old_adapter_still_answers(
            self, op, key, alt, monkeypatch, tmp_path):
        key = tuple(k.format(b=registry.backend()) if isinstance(k, str)
                    else k for k in key)
        path = tmp_path / "legacy_v1.json"
        path.write_text(json.dumps(
            {"version": 1, "winners": {repr(key): alt}}))
        self._no_measuring(monkeypatch, path)
        monkeypatch.setattr(registry.ops()[op], "candidates",
                            lambda ctx: ["xla", "alt"])
        hits = metrics.counter("autotune.disk_hits").value
        assert MEASURED[op]() == alt
        assert metrics.counter("autotune.disk_hits").value == hits + 1
        sp = metrics.spans(f"kernel.select:{op}")[-1]
        assert sp.args["source"] == "disk" and sp.args["pick"] == alt
        assert registry.table()[key] == (alt, {})

    @pytest.mark.parametrize("kind", ["pre-version", "future", "unversioned",
                                      "garbage"])
    def test_any_other_file_is_ignored_never_fatal(self, kind, monkeypatch,
                                                   tmp_path):
        winners = {repr(("paged", registry.backend(), 1, 2, 2, 1, 2,
                         "float32")): "alt"}
        path = tmp_path / "other.json"
        path.write_text({
            "pre-version": json.dumps(winners),     # the bare mapping
            "future": json.dumps({"version": 99, "winners": winners}),
            "unversioned": json.dumps({"winners": winners}),
            "garbage": "{not json"}[kind])
        monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE", str(path))
        monkeypatch.setattr(registry.ops()["paged_attention"], "candidates",
                            lambda ctx: ["xla", "alt"])
        measured = []
        monkeypatch.setattr(registry, "measure",
                            lambda *a, **kw: measured.append(1) or 0.001)
        assert _paged() in ("xla", "alt") and len(measured) == 2
        # ... and the measured winner replaced it with a version-1 table
        data = json.loads(path.read_text())
        assert data["version"] == 1 and len(data["winners"]) == 1


# ------------------------------------------------------------- ast-guard


# every kernel call site that must resolve its impl through
# registry.dispatch — a new hand-rolled dispatch branch fails here
DISPATCH_SITES = {
    "paddle_tpu/kernels/flash_attention.py": ["flash_attention_fn"],
    "paddle_tpu/kernels/paged_attention.py": ["paged_attention",
                                              "prefill_impl"],
    "paddle_tpu/kernels/sampling.py": ["fused_sample"],
    "paddle_tpu/nn/functional/attention.py": [
        "sequence_parallel_attention"],
    "paddle_tpu/models/gpt.py": ["_fused_ce_impl"],
    # an eager call site resolves ONCE, at construction — the selection
    # still routes through the registry
    "paddle_tpu/incubate/nn/__init__.py": ["__init__"],
}

# an op that is not routed in the module that implements (and registers)
# it: {op: the file that dispatches or counts it}. The model chooses its
# loss; `ssm2_scan` is counted by the helper it shares with `ssm.py`
DISPATCHED_ELSEWHERE = {"fused_ce": "paddle_tpu/models/gpt.py",
                        "ssm2_scan": "paddle_tpu/kernels/ssm.py"}

# where the ops that `registry.py` itself once listed are registered now
OP_HOMES = {
    "flash_attention": ("kernels", "flash_attention.py"),
    "paged_attention": ("kernels", "paged_attention.py"),
    "prefill_attention": ("kernels", "paged_attention.py"),
    "fused_ce": ("kernels", "fused_ce.py"),
    "fused_sampling": ("kernels", "sampling.py"),
    "sp_attention": ("nn", "functional", "attention.py"),
    "fused_layernorm": ("incubate", "nn", "__init__.py"),
}


def _package_sources():
    for dirpath, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    yield os.path.relpath(path, REPO), f.read()


def _registry_calls(tree, attr):
    """First arguments of every ``registry.<attr>(...)`` call: the
    literal, or None for a name computed at run time."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == attr \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "registry" and node.args:
            arg = node.args[0]
            yield arg.value if isinstance(arg, ast.Constant) else None


def _function_nodes(tree):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _calls_registry_dispatch(fn_node) -> bool:
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "dispatch" \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "registry":
            return True
    return False


def test_every_kernel_call_site_routes_through_the_registry():
    """AST guard (test_wall_budget.py style, no heavy imports): each
    migrated dispatch site's function body contains a
    ``registry.dispatch(...)`` call — removing one (or adding a parallel
    hand-rolled selector) fails here, not as a silent counter gap."""
    for rel, fns in DISPATCH_SITES.items():
        with open(os.path.join(REPO, rel)) as f:
            tree = ast.parse(f.read(), rel)
        found: dict = {}
        for n in _function_nodes(tree):
            found.setdefault(n.name, []).append(_calls_registry_dispatch(n))
        for fn in fns:
            assert any(found.get(fn, [])), (
                f"{rel}::{fn} no longer routes through registry.dispatch "
                f"(hand-rolled dispatch crept back in)")


def test_no_dispatch_counters_minted_outside_the_registry():
    """The ``kernel.dispatch.`` and legacy ``paged_attention.impl.``
    counter namespaces belong to registry.count() alone — a call site
    incrementing them directly would double-count or drift."""
    offenders = []
    for dirpath, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, name), REPO)
            if rel == os.path.join("paddle_tpu", "kernels", "registry.py"):
                continue
            with open(os.path.join(dirpath, name)) as f:
                src = f.read()
            if 'counter(f"kernel.dispatch.' in src \
                    or "counter('kernel.dispatch." in src \
                    or 'counter("kernel.dispatch.' in src \
                    or 'counter(f"paged_attention.impl.' in src:
                offenders.append(rel)
    assert not offenders, offenders


def test_every_op_is_registered_by_the_module_that_routes_it():
    """An op's registration sits in the module that implements it, beside
    the `dispatch` or `count` that names it (`DISPATCHED_ELSEWHERE` lists
    the two that are routed from another file): no central list of ops to
    drift from the call sites, and none registered twice."""
    registered, routed = {}, {}
    for rel, src in _package_sources():
        tree = ast.parse(src, rel)
        for op in _registry_calls(tree, "register_op"):
            assert op not in registered, (op, rel, registered[op])
            registered[op] = rel
        routed[rel] = {op for attr in ("dispatch", "count")
                       for op in _registry_calls(tree, attr)}
    assert set(registry.ops()) <= set(registered)
    for op, home in OP_HOMES.items():
        assert registered.get(op) == os.path.join("paddle_tpu", *home), op
    for op, rel in registered.items():
        site = DISPATCHED_ELSEWHERE.get(op, rel)
        # None: the module routes an op it names at run time (ssm.py)
        assert routed[site] & {op, None}, (
            f"{op} is registered in {rel}, which neither dispatches nor "
            f"counts it")


def test_registry_names_no_op_and_nothing_imports_the_old_adapter():
    """The arrows point one way: `registry.py` imports no module of
    `paddle_tpu.kernels`, and the adapter module that sat above it (and
    was imported by it) is gone for good."""
    def imported(tree):
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                yield from (a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom) and node.module:
                yield node.module
                yield from (f"{node.module}.{a.name}" for a in node.names)

    adapter = "autotune"
    for rel, src in _package_sources():
        names = set(imported(ast.parse(src, rel)))
        assert f"paddle_tpu.kernels.{adapter}" not in names, rel
        if rel == os.path.join("paddle_tpu", "kernels", "registry.py"):
            assert not [n for n in names
                        if n.startswith("paddle_tpu.kernels")], names
            assert "register_op(\"" not in src       # it registers none
    assert not os.path.exists(os.path.join(
        REPO, "paddle_tpu", "kernels", f"{adapter}.py"))
