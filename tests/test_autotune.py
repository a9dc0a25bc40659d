"""Kernel autotuner (ref phi/kernels/autotune): measured selection, caching,
backend gating by name."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels import autotune


@pytest.fixture(autouse=True)
def _clean_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


class TestFlashWinner:
    def test_cpu_backend_measures_xla_and_dense_only(self):
        # off-TPU: no Pallas candidates; xla + dense are measured for real
        calls = []

        def run_impl(impl, q, k, v):
            calls.append(impl)
            return q * 1.0

        w = autotune.flash_winner((1, 1, 8, 4), (1, 1, 8, 4), jnp.float32,
                                  False, True, run_impl)
        assert w in ("xla", "dense")
        assert set(calls) == {"xla", "dense"}   # no pallas impl executed

    def test_measured_selection_and_cache(self, monkeypatch):
        # pretend we're on real TPU so multiple candidates are offered
        monkeypatch.setattr(autotune, "_backend_kind", lambda: "tpu")
        timings = {"xla": 5.0, "dense": 4.0, "mosaic": 1.0, "splash": 3.0, "authored": 2.0}
        def run_impl(impl, q, k, v):
            return q

        # candidates are measured in _flash_candidates order
        order = iter(["xla", "dense", "mosaic", "splash", "authored"])

        def fake_measure2(fn, args, **kw):
            return timings[next(order)]

        monkeypatch.setattr(autotune, "_measure", fake_measure2)
        w = autotune.flash_winner((1, 1, 128, 64), (1, 1, 128, 64),
                                  jnp.float32, True, True, run_impl)
        assert w == "mosaic"          # the fastest fake timing
        # second call: cache hit, no re-measure (order iterator exhausted)
        w2 = autotune.flash_winner((1, 1, 128, 64), (1, 1, 128, 64),
                                   jnp.float32, True, True, run_impl)
        assert w2 == "mosaic"
        key = next(iter(autotune.cache_table()))
        assert autotune.cache_table()[key][0] == "mosaic"

    def test_failing_candidate_is_recorded_and_warned(self, monkeypatch,
                                                      caplog):
        """A candidate the device refuses is not data to be dropped: its
        error lands in the table entry in place of a time and is logged
        at WARNING, so whoever reads `registry.table()` sees the refusal."""
        monkeypatch.setattr(autotune, "_backend_kind", lambda: "tpu")
        monkeypatch.setattr(autotune, "_measure",
                            lambda fn, args, **kw: (fn(*args), 1.0)[1])

        def run_impl(impl, q, k, v):
            if impl != "xla":
                raise RuntimeError("mosaic lowering failed")
            return q * 1.0

        with caplog.at_level("WARNING", logger="paddle_tpu.kernels.registry"):
            w = autotune.flash_winner((1, 1, 16, 8), (1, 1, 16, 8),
                                      jnp.float32, False, True, run_impl)
        assert w == "xla"
        (winner, per_impl), = autotune.cache_table().values()
        assert winner == "xla" and per_impl["xla"] == 1.0
        for impl in ("dense", "mosaic", "splash", "authored"):
            assert per_impl[impl] == "RuntimeError: mosaic lowering failed"
        warned = [r for r in caplog.records if r.levelname == "WARNING"]
        assert len(warned) == 4 and "mosaic lowering failed" in warned[0].message

    def test_every_candidate_failing_raises(self, monkeypatch):
        monkeypatch.setattr(autotune, "_backend_kind", lambda: "tpu")

        def run_impl(impl, q, k, v):
            raise RuntimeError(f"{impl} refused")

        with pytest.raises(RuntimeError, match="every candidate"):
            autotune.flash_winner((1, 1, 16, 8), (1, 1, 16, 8), jnp.float32,
                                  False, True, run_impl)
        assert autotune.cache_table() == {}


class TestEndToEnd:
    def test_auto_flag_routes_through_autotuner_on_cpu(self):
        """flag=auto on CPU: single candidate, no measurement, correct out."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.framework.flags import set_flags
        set_flags({"tpu_flash_impl": "auto"})
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(1, 8, 2, 4).astype(np.float32))
        out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        assert np.isfinite(np.asarray(out._data)).all()
        assert len(autotune.cache_table()) >= 1


class TestMeasurementInsideATrace:
    def test_candidates_execute_while_a_program_is_being_traced(self,
                                                                monkeypatch):
        """Selections run at trace time of a step program. The candidates
        must EXECUTE there (concrete arrays, a real clock), not be staged
        into the outer trace where `block_until_ready` is a no-op."""
        monkeypatch.setattr(autotune, "_backend_kind", lambda: "tpu")
        monkeypatch.setattr(
            autotune, "_flash_candidates",
            lambda *a, **k: ["xla", "dense"])
        concrete = []

        def measure(fn, args, **kw):
            leaves = jax.tree_util.tree_leaves((args, fn(*args)))
            concrete.append(not any(isinstance(a, jax.core.Tracer)
                                    for a in leaves))
            return 1.0

        monkeypatch.setattr(autotune, "_measure", measure)

        @jax.jit
        def program(x):
            autotune.flash_winner((1, 1, 16, 8), (1, 1, 16, 8), jnp.float32,
                                  False, False, lambda i, q, k, v: q * 1.0)
            return x + 1

        program(jnp.zeros(2))
        assert concrete == [True, True]     # one per candidate

    def test_mesh_partitioned_call_offers_only_xla_arms(self):
        assert autotune._flash_candidates(
            "tpu", True, (1, 1, 128, 64), (1, 1, 128, 64)) == \
            ["xla", "dense", "mosaic", "splash", "authored"]
        assert autotune._flash_candidates(
            "tpu", True, (1, 1, 128, 64), (1, 1, 128, 64),
            partitioned=True) == ["xla", "dense"]
