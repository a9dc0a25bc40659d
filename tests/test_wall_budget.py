"""Tier-1 wall-budget audit guard (PR 12 satellite).

The tier-1 suite runs under a hard 870 s driver timeout and measured
~893 s clean before this audit — past the budget. The audit
(`pytest --durations` over the full suite and the chaos suites) moved
the redundant heavy items to ``slow`` (nightly ``--runslow`` keeps
them), each with a cheaper sibling pinning its invariant every tier-1
run:

- ``test_optest_autosweep.py::test_autosweep_eager_static_grad``
  (~46 s): the per-op to_static + backward arms; tier-1 keeps
  ``test_autosweep_eager`` (whole-long-tail eager rot guard, ~12 s) and
  the curated sweeps keep static/grad parity for meaningful signatures.
- ``test_train_chaos.py::test_kill9_resume_bit_identical`` (~20 s): the
  REAL ``kill -9`` subprocess drill; resume bit-parity stays pinned by
  ``test_fit_resume_parity`` and bench --smoke's ``resume_ok``.
- ``test_migration.py::test_every_migration_step_boundary_is_token_identical``
  (~4 s): the 1/2/5/8-boundary sweep; one boundary stays pinned by
  ``test_mid_decode_export_resumes_token_identical``.
- ``test_aux_systems.py`` ``TestModelZoo::test_forward_shapes[mobilenet_v2]``
  (~9 s): mobilenet_v1 keeps the family's forward-shape pin.

This module is the HEADROOM ASSERTION: it fails the moment someone
un-marks one of those items (tipping the tier-1 wall back toward the
timeout) without re-doing the audit. It checks the SOURCE via ast — no
import of the heavy modules, sub-second.
"""
import ast
import os

import pytest

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

# file -> test functions that MUST carry @pytest.mark.slow
SLOW_PINNED = {
    "test_train_chaos.py": ["test_kill9_resume_bit_identical"],
    "test_migration.py": [
        "test_every_migration_step_boundary_is_token_identical"],
    "test_optest_autosweep.py": ["test_autosweep_eager_static_grad"],
    # nothing pinned since PR 21 deleted the second bench --smoke
    # subprocess; the file stays a case so that a new pin has its place
    "test_observability.py": [],
    # PR 14 audit: the REAL multi-process elastic drills spawn 4-6 jax
    # subprocesses (~40 s); each invariant keeps a cheap in-process
    # sibling in tier-1 (see the sibling map below).
    "test_train_elastic.py": [
        "test_kill9_one_of_four_relaunches_at_dp2_bit_identical",
        "test_sigterm_any_rank_drains_whole_fleet_to_complete_checkpoint"],
    # PR 16 audit: the stitched-trace drill spawns 3 serve subprocesses
    # plus an in-test router (~12 s), and the shared-snapshot autoscale
    # drill runs the full 1->3->1 cycle under client load (~8 s); both
    # keep cheap in-process siblings in tier-1 (see the sibling map).
    "test_fleet_observability.py": [
        "test_stitched_trace_three_processes_with_migration",
        "test_scale_1_3_1_on_shared_fleet_snapshot"],
    # PR 17 audit: the streaming-prefill tier drill builds TWO engines
    # and drives the full chunk-record pipeline (~8 s); its invariant
    # (re-upload is bit-identical, tail-only) keeps the cheap
    # prefill_export sibling in tier-1 (see the sibling map).
    "test_kv_tiers.py": [
        "test_stream_prefill_reuploads_token_identical"],
}

# file -> pytest.param values that MUST carry marks=pytest.mark.slow
SLOW_PARAM_PINNED = {
    "test_aux_systems.py": ["mobilenet_v2"],
}


def _is_slow_mark(dec) -> bool:
    """True for a ``pytest.mark.slow`` decorator/marks node."""
    return (isinstance(dec, ast.Attribute) and dec.attr == "slow"
            and isinstance(dec.value, ast.Attribute)
            and dec.value.attr == "mark")


def _parse(fname):
    with open(os.path.join(_TESTS_DIR, fname)) as f:
        return ast.parse(f.read())


def _slow_marked_defs(tree) -> set:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and any(_is_slow_mark(d) for d in node.decorator_list):
            out.add(node.name)
    return out


def _slow_marked_params(tree) -> set:
    """String literals appearing as the first arg of a ``pytest.param``
    call whose ``marks=`` includes ``pytest.mark.slow``."""
    out = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "param"):
            continue
        marks = [kw.value for kw in node.keywords if kw.arg == "marks"]
        flat = []
        for m in marks:
            flat.extend(m.elts if isinstance(m, (ast.List, ast.Tuple))
                        else [m])
        if not any(_is_slow_mark(m) for m in flat):
            continue
        for a in node.args:
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                out.add(a.value)
    return out


@pytest.mark.parametrize("fname", sorted(set(SLOW_PINNED)
                                         | set(SLOW_PARAM_PINNED)))
def test_audited_heavy_items_stay_marked_slow(fname):
    tree = _parse(fname)
    missing = [t for t in SLOW_PINNED.get(fname, [])
               if t not in _slow_marked_defs(tree)]
    missing += [p for p in SLOW_PARAM_PINNED.get(fname, [])
                if p not in _slow_marked_params(tree)]
    assert not missing, (
        f"{fname}: {missing} lost their slow mark — these are the "
        f"wall-audited heavy items (see this module's docstring); "
        f"un-marking them spends tier-1's timeout headroom. Re-run the "
        f"audit (pytest --durations=30) before moving them back.")


def test_tier1_keeps_a_cheap_sibling_for_each_audited_item():
    """The audit's other half: every slow-marked heavy item must leave
    its CHEAP sibling in tier-1 — deleting the sibling would silently
    drop the invariant from every CI run, which is worse than the wall
    regression the marks prevent."""
    siblings = {
        "test_optest_autosweep.py": ["test_autosweep_eager"],
        "test_train_chaos.py": ["test_fit_resume_parity"],
        "test_observability.py": ["test_bench_smoke_emits_structured_json"],
        "test_migration.py": [
            "test_mid_decode_export_resumes_token_identical"],
        # the elastic kill/relaunch drill decomposes into these tier-1
        # pins: typed detection, fleet-wide publication, restart policy,
        # and split-step loss parity (the retrace pin lives in
        # test_no_retrace.py::test_elastic_split_step_compiles_once_then_
        # never, which tier-1 runs whole)
        "test_train_elastic.py": [
            "test_monitor_silent_peer_is_typed_peer_lost",
            "test_multihost_partitioned_save_is_complete_only_with_all_ranks",
            "test_controller_relaunches_at_surviving_world",
            "test_split_step_bit_identical_to_fused"],
        # the 3-process stitched-trace drill decomposes into these
        # tier-1 pins: router re-parenting, wire trace export + stitch,
        # and migration trace carry-over; the shared-snapshot autoscale
        # drill keeps its observation-equivalence sibling
        "test_fleet_observability.py": [
            "test_router_reparents_span_chain",
            "test_trace_export_via_router_and_stitch",
            "test_warm_migration_peer_carries_original_trace",
            "test_autoscaler_observes_identically_via_fleet_snapshot"],
        # the streaming-prefill tier drill decomposes into these tier-1
        # pins: the handoff-export re-upload (same spill -> re-upload ->
        # bit-identical-pages invariant, one engine, no record stream)
        # and the submit-path tail-only token-identity headline
        "test_kv_tiers.py": [
            "test_prefill_export_reuploads_from_tier",
            "test_host_tier_hit_token_identical_tail_only"],
    }
    for fname, names in siblings.items():
        tree = _parse(fname)
        defs = {n.name for n in ast.walk(tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        slow = _slow_marked_defs(tree)
        for name in names:
            assert name in defs, f"{fname}: cheap sibling {name} deleted"
            assert name not in slow, \
                f"{fname}: cheap sibling {name} was itself marked slow"
