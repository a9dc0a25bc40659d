"""ONE kernel registry (r15, `paddle_tpu/kernels/registry.py`): dispatch,
viability, the `kernel.dispatch.{op}.{impl}` counters, legacy winner-file
migration, and the ast-guard pinning that every kernel call site routes
through the registry instead of hand-rolled dispatch glue."""
import ast
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import autotune, registry
from paddle_tpu.observability import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


# ------------------------------------------------------------- dispatch


class TestDispatch:
    def test_unknown_op_and_unknown_impl_are_loud(self):
        with pytest.raises(KeyError, match="unknown kernel op"):
            registry.dispatch("no_such_op")
        with pytest.raises(ValueError, match="no impl"):
            registry.dispatch("paged_attention", forced="bogus")

    def test_forced_outside_viable_set_allowed_by_default(self):
        # interpret-mode parity testing forces pallas off-TPU on purpose
        assert registry.dispatch("paged_attention", forced="pallas") \
            == "pallas"

    def test_require_viable_degrades_to_first_candidate(self):
        # the fused-CE rule: "fused" wanted but mp>1 -> dense
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
        monkeypatch.setattr(autotune, "_backend_kind", lambda: "tpu")
        op = registry.ops()["prefill_attention"]
        assert op.candidates({"parity": True}) == ["xla", "pallas"]
        assert op.candidates({"parity": False}) == ["xla"]

    def test_auto_prefill_selection_respects_parity_gate(self, monkeypatch):
        """Review-round regression: the AUTO path must honor the parity
        gate too — `prefill_winner` filters its candidates (and keys the
        table distinctly), so a narrowing-pool one-shot prefill can never
        measure-and-pick the pool-reading pallas arm, even on a backend
        where pallas wins every race."""
        from paddle_tpu.kernels import paged_attention as pa
        monkeypatch.setattr(autotune, "_backend_kind", lambda: "tpu")
        monkeypatch.setattr(
            autotune, "_measure",
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

    def test_winner_outside_viable_set_degrades(self):
        """Defense in depth: an adapter whose candidate list drifts from
        the dispatch-level viability ctx cannot smuggle a non-viable impl
        past the gate."""
        assert registry.dispatch("prefill_attention", forced="auto",
                                 ctx={"parity": False},
                                 winner=lambda: "pallas") == "xla"

    def test_every_builtin_op_registered(self):
        have = set(registry.ops())
        assert {"flash_attention", "paged_attention", "prefill_attention",
                "fused_sampling", "sp_attention", "fused_ce",
                "fused_layernorm", "fused_rope"} <= have


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


class TestLegacyWinnerFiles:
    """Satellite: legacy PADDLE_AUTOTUNE_CACHE files migrate into the
    registry's table on first load — old winners survive, corrupt/stale
    never fatal (the PR 7 contract held across the refactor)."""

    def _consult(self, monkeypatch, path):
        """Ask paged_winner with 2 candidates and a measurer that FAILS
        the test if called — a disk hit must skip measurement."""
        monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE", str(path))
        monkeypatch.setattr(autotune, "_paged_candidates",
                            lambda backend: ["xla", "alt"])
        monkeypatch.setattr(
            autotune, "_measure",
            lambda *a, **kw: pytest.fail("disk winner ignored: measured"))
        return autotune.paged_winner(
            1, 2, 2, 1, 2, "float32",
            lambda impl, q, k, v, pt, pos: q)

    def test_v1_file_written_by_the_old_autotuner_loads_asis(
            self, monkeypatch, tmp_path):
        # the EXACT key format kernels/autotune.py wrote before the
        # registry existed (and still writes) — byte-for-byte
        backend = autotune._backend_kind()
        key = ("paged", backend, 1, 2, 2, 1, 2, "float32")
        path = tmp_path / "legacy_v1.json"
        path.write_text(json.dumps(
            {"version": 1, "winners": {repr(key): "alt"}}))
        assert self._consult(monkeypatch, path) == "alt"
        assert metrics.counter("autotune.disk_hits").value >= 1

    def test_preversion_bare_mapping_migrates_counted_once(
            self, monkeypatch, tmp_path):
        backend = autotune._backend_kind()
        key = ("paged", backend, 1, 2, 2, 1, 2, "float32")
        path = tmp_path / "ancient.json"
        path.write_text(json.dumps({repr(key): "alt", "garbage": 3}))
        before = metrics.counter("autotune.disk_migrated").value
        assert self._consult(monkeypatch, path) == "alt"
        assert metrics.counter("autotune.disk_migrated").value \
            == before + 1
        # review-round regression: a STORE re-reads the (still legacy)
        # file without re-counting — each migrated entry counts ONCE
        registry._disk_store(("x", "y"), "xla")
        assert metrics.counter("autotune.disk_migrated").value \
            == before + 1

    def test_future_version_and_garbage_ignored_never_fatal(
            self, monkeypatch, tmp_path):
        path = tmp_path / "future.json"
        path.write_text(json.dumps({"version": 99, "winners": {"x": "y"}}))
        monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE", str(path))
        monkeypatch.setattr(autotune, "_paged_candidates",
                            lambda backend: ["xla", "alt"])
        measured = []
        monkeypatch.setattr(autotune, "_measure",
                            lambda *a, **kw: measured.append(1) or 0.001)
        w = autotune.paged_winner(1, 2, 2, 1, 2, "float32",
                                  lambda impl, q, k, v, pt, pos: q)
        assert w in ("xla", "alt") and len(measured) == 2

    def test_registry_and_autotune_share_one_table(self):
        registry._TABLE[("x",)] = ("xla", {})
        assert autotune._CACHE is registry._TABLE
        assert autotune.cache_table()[("x",)] == ("xla", {})
        autotune.clear_cache()
        assert registry.table() == {}


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
    # eager call sites resolve ONCE (construction / per-process cache) —
    # the selection still routes through the registry
    "paddle_tpu/incubate/nn/__init__.py": ["__init__", "_rope_impl"],
}


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


def test_legacy_winner_helpers_live_only_in_the_adapter():
    """`flash_winner`/`paged_winner`/`prefill_winner` are op ADAPTERS:
    defined in kernels/autotune.py only, and every other module reaches
    them solely as the measured-selection hook passed to
    registry.dispatch (the four legacy dispatch sites are gone)."""
    defs = []
    for dirpath, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for n in _function_nodes(tree):
                if n.name in ("flash_winner", "paged_winner",
                              "prefill_winner"):
                    defs.append(os.path.relpath(path, REPO))
    assert set(defs) == {os.path.join("paddle_tpu", "kernels",
                                      "autotune.py")}, defs
