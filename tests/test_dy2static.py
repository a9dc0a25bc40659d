"""Dy2static control-flow conversion (round-2 VERDICT #8; ref the
`dygraph_to_static` suite): eager-vs-captured parity for data-dependent
if/while, explicit cond/while_loop ops, clear unsupported errors."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def _t(x):
    return paddle.to_tensor(np.asarray(x, np.float32))


class TestExplicitOps:
    def test_cond_concrete_and_traced(self):
        def f(x):
            return paddle.static.nn.cond(
                x.sum() > 0, lambda: x * 2, lambda: x - 1)

        x = _t([1.0, 2.0])
        np.testing.assert_allclose(np.asarray(f(x)._data), [2.0, 4.0])
        xneg = _t([-1.0, -2.0])
        np.testing.assert_allclose(np.asarray(f(xneg)._data), [-2.0, -3.0])

        @paddle.jit.to_static
        def g(x):
            return paddle.static.nn.cond(
                x.sum() > 0, lambda: x * 2, lambda: x - 1)

        np.testing.assert_allclose(np.asarray(g(x)._data), [2.0, 4.0])
        np.testing.assert_allclose(np.asarray(g(xneg)._data), [-2.0, -3.0])

    def test_cond_grads_flow(self):
        x = _t([3.0, -1.0])
        x.stop_gradient = False
        out = paddle.jit.ifelse(x.sum() > 0,
                                lambda a: (a * 3,),
                                lambda a: (a * 5,), (x,))[0]
        # concrete pred -> python path; grads via normal tape
        out.sum().backward()
        np.testing.assert_allclose(np.asarray(x.grad._data), [3.0, 3.0])

    def test_traced_cond_grads(self):
        @paddle.jit.to_static
        def step(x):
            out = paddle.jit.ifelse(x.sum() > 0,
                                    lambda a: (a * 3,),
                                    lambda a: (a * 5,), (x,))[0]
            loss = out.sum()
            loss.backward()
            return loss, x.grad

        x = _t([3.0, -1.0])
        x.stop_gradient = False
        loss, g = step(x)
        np.testing.assert_allclose(np.asarray(g._data), [3.0, 3.0])
        xneg = _t([-3.0, -1.0])
        xneg.stop_gradient = False
        loss, g = step(xneg)
        np.testing.assert_allclose(np.asarray(g._data), [5.0, 5.0])

    def test_while_loop(self):
        def double_until(x):
            return paddle.static.nn.while_loop(
                lambda v: v.sum() < 100.0, lambda v: v * 2, [x])[0]

        # doubling stops once the sum reaches 100: [1,2]->...->[64,128]
        out = double_until(_t([1.0, 2.0]))
        np.testing.assert_allclose(np.asarray(out._data), [64.0, 128.0])

        @paddle.jit.to_static
        def g(x):
            return paddle.static.nn.while_loop(
                lambda v: v.sum() < 100.0, lambda v: v * 2, [x])[0]

        np.testing.assert_allclose(np.asarray(g(_t([1.0, 2.0]))._data),
                                   [64.0, 128.0])


class TestAutoConversion:
    def test_data_dependent_if_auto_converts(self):
        """The canonical dygraph_to_static if/else case runs unmodified."""
        def model(x):
            if x.mean() > 0:
                y = x + 10.0
            else:
                y = x - 10.0
            return y * 2

        xs = [_t([1.0, 3.0]), _t([-5.0, -1.0])]
        eager = [np.asarray(model(x)._data) for x in xs]

        compiled = paddle.jit.to_static(model)
        got = [np.asarray(compiled(x)._data) for x in xs]
        for e, g in zip(eager, got):
            np.testing.assert_allclose(g, e)

    def test_data_dependent_while_auto_converts(self):
        def model(x):
            s = x
            while s.sum() < 50.0:
                s = s * 2
            return s + 1

        xs = [_t([1.0, 2.0]), _t([30.0, 30.0])]
        eager = [np.asarray(model(x)._data) for x in xs]
        compiled = paddle.jit.to_static(model)
        got = [np.asarray(compiled(x)._data) for x in xs]
        for e, g in zip(eager, got):
            np.testing.assert_allclose(g, e)

    def test_nested_if_in_while(self):
        def model(x):
            s = x
            n = paddle.to_tensor(np.float32(0.0))
            while s.sum() < 40.0:
                if s.mean() > 2.0:
                    s = s * 3
                else:
                    s = s * 2
                n = n + 1
            return s, n

        x = _t([1.0, 1.5])
        es, en = model(x)
        cs, cn = paddle.jit.to_static(model)(x)
        np.testing.assert_allclose(np.asarray(cs._data),
                                   np.asarray(es._data))
        np.testing.assert_allclose(np.asarray(cn._data),
                                   np.asarray(en._data))

    def test_branch_assigning_closure_weights(self):
        """Converted branches may READ closure vars (layer weights)."""
        paddle.seed(0)
        lin = nn.Linear(4, 4)

        def model(x):
            if x.mean() > 0:
                h = lin(x)
            else:
                h = lin(x) * 0.5
            return h.sum()

        x = _t(np.ones((2, 4)))
        eager = float(model(x))
        got = float(paddle.jit.to_static(model)(x))
        np.testing.assert_allclose(got, eager, rtol=1e-6)

    def test_layer_params_get_grads_inside_traced_branch(self):
        """Weights reached THROUGH a Layer operand must receive gradients
        (round-3 review: they were silently zero)."""
        paddle.seed(0)
        lin = nn.Linear(4, 4)

        def eager_ref(x):
            h = lin(x) if float(x.mean()) > 0 else lin(x) * 0.5
            return h.sum()

        x = _t(np.ones((2, 4)))
        eager_ref(x).backward()
        want = np.asarray(lin.weight.grad._data).copy()
        lin.clear_gradients()

        @paddle.jit.to_static
        def step(x):
            if x.mean() > 0:
                h = lin(x)
            else:
                h = lin(x) * 0.5
            loss = h.sum()
            loss.backward()
            return loss, lin.weight.grad

        _, g = step(x)
        assert g is not None, "no grad reached the layer weight"
        np.testing.assert_allclose(np.asarray(g._data), want, rtol=1e-5)

    def test_while_counter_auto_promotes(self):
        """Python int counters in a traced while body are promoted to
        loop-carried Tensors instead of silently freezing."""
        def model(x):
            s = x
            i = 0
            while s.sum() < 50.0:
                s = s * 2
                i = i + 1
            return s, i

        x = _t([1.0, 2.0])
        es, ei = model(x)
        cs, ci = paddle.jit.to_static(model)(x)
        np.testing.assert_allclose(np.asarray(cs._data),
                                   np.asarray(es._data))
        assert int(np.asarray(ci._data)) == ei

    def test_python_condition_stays_python(self):
        """Concrete (non-tensor) conditions keep plain Python semantics
        through the same transformed code."""
        def model(x, flag):
            if flag:
                y = x + 1
            else:
                y = x - 1
            return y

        f = paddle.jit.to_static(model)
        np.testing.assert_allclose(np.asarray(f(_t([1.0]), True)._data),
                                   [2.0])

    def test_unconvertible_raises_clearly(self):
        """return inside a data-dependent branch: not converted, and the
        failure names the problem instead of a raw tracer error."""
        from paddle_tpu.jit.dy2static import DataDependentControlFlowError

        def model(x):
            if x.mean() > 0:
                return x * 2
            return x - 2

        f = paddle.jit.to_static(model)
        with pytest.raises(DataDependentControlFlowError,
                           match="cond|branch|condition"):
            f(_t([1.0, 2.0]))


class TestConverterUnit:
    def test_convert_to_static_source_shape(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def fn(x):
            if x.mean() > 0:
                y = x * 2
            else:
                y = x / 2
            return y

        conv = convert_to_static(fn)
        x = _t([4.0])
        np.testing.assert_allclose(np.asarray(conv(x)._data), [8.0])
        np.testing.assert_allclose(np.asarray(conv(_t([-4.0]))._data),
                                   [-2.0])


class TestTrainingIntegration:
    def test_branching_train_step_converges(self):
        """The round-3 regression: a branch READING a local tensor (loss)
        must stay differentiable — loads enter as explicit operands, not
        closure captures, or backward silently produces no grads."""
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(),
                              nn.Linear(64, 2))
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=model.parameters())
        loss_fn = nn.CrossEntropyLoss()
        rng = np.random.RandomState(0)
        X = paddle.to_tensor(rng.randn(128, 16).astype(np.float32))
        Y = paddle.to_tensor(rng.randint(0, 2, 128).astype(np.int64))

        @paddle.jit.to_static
        def step(x, y):
            loss = loss_fn(model(x), y)
            if loss > 1.0:
                scaled = loss * 0.5
            else:
                scaled = loss
            scaled.backward()
            opt.step()
            opt.clear_grad()
            return loss

        losses = [float(step(X, Y)) for _ in range(25)]
        assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


class TestEscapeConversion:
    """break/continue/return conversion (round-3 VERDICT missing #6; ref
    `jit/dy2static/break_continue_transformer.py:96`): escapes become
    loop-carried tensor flags, statements after a possible escape are
    guarded, and function-level returns funnel into one synthesized return."""

    def test_break_concrete(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            i, s = 0, 0
            while i < n:
                if i == 3:
                    break
                s += i
                i += 1
            return s

        assert convert_to_static(f)(10) == f(10) == 3

    def test_continue_concrete(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            i, s = 0, 0
            while i < n:
                i += 1
                if i % 2 == 0:
                    continue
                s += i
            return s

        assert convert_to_static(f)(6) == f(6)

    def test_return_in_loop(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            i = 0
            while i < n:
                if i == 4:
                    return i * 100
                i += 1
            return -1

        g = convert_to_static(f)
        assert g(10) == f(10) == 400
        assert g(3) == f(3) == -1

    def test_traced_break_matches_eager(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x):
            i = paddle.to_tensor(0)
            s = paddle.to_tensor(0.0)
            while i < 10:
                if paddle.sum(x) * 0 + i == 5:  # traced break condition
                    break
                s = s + paddle.sum(x)
                i = i + 1
            return s

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x):
            return g(x)

        x = _t([1.0, 1.0, 1.0])
        np.testing.assert_allclose(float(step(x)), float(f(x)), rtol=1e-6)

    def test_bounded_while_reverse_mode(self):
        """maximum_trip_count -> scan lowering, reverse-differentiable
        (the WhileGradOp analog, ref `while_op.cc:348`)."""
        from paddle_tpu.jit.dy2static import while_loop

        w = _t(2.0)
        w.stop_gradient = False
        _, acc = while_loop(lambda i, a: i < 3,
                            lambda i, a: (i + 1, a * w),
                            [paddle.to_tensor(0), w * 1.0],
                            maximum_trip_count=5)
        acc.backward()
        assert abs(float(acc) - 16.0) < 1e-5          # w^4
        assert abs(float(w.grad) - 32.0) < 1e-5       # 4 w^3

    def test_unbounded_traced_while_with_grads_raises(self):
        """round-3 VERDICT weak #5: forward-only while under an active tape
        must raise loudly, not silently zero the gradients."""
        from paddle_tpu.jit.dy2static import whileloop

        w = _t(2.0)
        w.stop_gradient = False

        @paddle.jit.to_static
        def bad(w):
            out = whileloop(lambda i, a: i < 3,
                            lambda i, a: (i + 1, a * 2.0),
                            (paddle.to_tensor(0), w * 1.0))
            return out[1]

        with pytest.raises(Exception, match="FORWARD-ONLY"):
            bad(w)

    def test_break_in_nested_while(self):
        """Escapes inside NESTED loops: flags are hoisted to function top
        (the outer loop carries them) and belong to the inner loop."""
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            i, s = 0, 0
            while i < n:
                j = 0
                while j < 10:
                    if j == 2:
                        break
                    j += 1
                    s += 1
                i += 1
            return s

        assert convert_to_static(f)(3) == f(3)

    def test_return_in_nested_while(self):
        """A return from an inner loop must break EVERY enclosing loop
        (ret-flag propagation) and skip the trailing return."""
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            i = 0
            while i < n:
                j = 0
                while j < 10:
                    if i * 10 + j == 13:
                        return i * 100 + j
                    j += 1
                i += 1
            return -1

        g = convert_to_static(f)
        assert g(5) == f(5) == 103
        assert g(1) == f(1) == -1

    def test_continue_in_nested_while_with_tail_code(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            tot, i = 0, 0
            while i < n:
                j, acc = 0, 0
                while j < 4:
                    j += 1
                    if j % 2 == 0:
                        continue
                    acc += j
                tot += acc
                i += 1
            return tot

        assert convert_to_static(f)(3) == f(3)

    def test_traced_while_with_unbound_carried_var_raises_clearly(self):
        """Body-start initialization of a carried var is legal Python when
        the loop is concrete; a TRACED loop must raise naming the var."""
        import numpy as np
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x):
            i = paddle.to_tensor(0)
            while i < x.sum():          # traced condition
                j = paddle.to_tensor(1)
                i = i + j
            return i

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x):
            return g(x)

        with pytest.raises(Exception, match="unbound"):
            step(_t([5.0]))


class TestForLoopConversion:
    """for-over-range conversion (r4 VERDICT missing #3; ref
    ForToWhileTransformer `jit/dy2static/break_continue_transformer.py:36`,
    `loop_transformer.py:517`): the counter advances before the body
    (continue-safe) and data-dependent trip counts become carried tensors."""

    def test_concrete_for_with_break_continue(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            s = 0
            for i in range(n):
                if i % 2 == 0:
                    continue
                if i > 7:
                    break
                s += i
            return s

        assert convert_to_static(f)(12) == f(12)

    def test_concrete_negative_step(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(a, b):
            s = 0
            for i in range(a, b, -2):
                s += i
            return s

        assert convert_to_static(f)(9, 0) == f(9, 0)

    def test_traced_stop_matches_eager(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x, n):
            s = x * 0.0
            for i in range(n):
                s = s + x * i
            return s

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x, n):
            return g(x, n)

        x = _t(2.0)
        for nv in (0, 1, 5):
            want = float(f(x, nv))
            got = float(step(x, paddle.to_tensor(nv)))
            np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_traced_for_auto_converts_through_to_static(self):
        """range(traced) inside a plain to_static fn triggers the retry
        (Tensor.__index__ raises the conversion signal)."""

        @paddle.jit.to_static
        def step(x, n):
            s = x * 0.0
            for i in range(n):
                s = s + x
            return s

        x = _t(3.0)
        assert float(step(x, paddle.to_tensor(4))) == 12.0

    def test_traced_for_with_break_grad_checked(self):
        """Data-dependent for + break, reverse-differentiable under
        FLAGS_dy2static_max_trip_count (bounded scan lowering)."""
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x, n):
            s = x * 0.0
            for i in range(n):
                if i == 7:
                    break
                s = s + x * i
            return s

        g = convert_to_static(f)
        set_flags({"FLAGS_dy2static_max_trip_count": 16})
        try:
            @paddle.jit.to_static
            def step(x, n):
                loss = g(x, n)
                loss.backward()
                return loss, x.grad

            x = _t(2.0)
            x.stop_gradient = False
            loss, grad = step(x, paddle.to_tensor(5))
            # s = x*(0+1+2+3+4) -> ds/dx = 10
            np.testing.assert_allclose(float(loss), 20.0, rtol=1e-6)
            np.testing.assert_allclose(float(grad), 10.0, rtol=1e-6)
        finally:
            set_flags({"FLAGS_dy2static_max_trip_count": 0})

    def test_exceeding_flag_bound_fails_loudly(self):
        """r5 advisor (medium): a traced loop whose true trip count exceeds
        FLAGS_dy2static_max_trip_count must RAISE at run time, not silently
        return the truncated result — truncation is indistinguishable from
        a correct answer. The in-bound path stays silent and correct."""
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x, n):
            s = x * 0.0
            i = paddle.to_tensor(0)
            while i < n:
                s = s + x
                i = i + 1
            return s

        g = convert_to_static(f)
        set_flags({"FLAGS_dy2static_max_trip_count": 4})
        try:
            @paddle.jit.to_static
            def step(x, n):
                return g(x, n)

            x = _t(2.0)
            # within the bound: correct and quiet (TRACED: n is a tensor
            # input, so the while lowers to the bounded scan)
            np.testing.assert_allclose(
                float(step(x, paddle.to_tensor(3))), 6.0, rtol=1e-6)
            # beyond the bound: the post-scan cond assert fires (surfaced
            # through jax.debug.callback as a runtime error whose message
            # names the flag)
            with pytest.raises(Exception, match="dy2static_max_trip_count"):
                float(step(x, paddle.to_tensor(9)))
        finally:
            set_flags({"FLAGS_dy2static_max_trip_count": 0})

    def test_flag_does_not_cap_concrete_loops(self):
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(n):
            s = 0
            for i in range(n):
                s += 1
            return s

        set_flags({"FLAGS_dy2static_max_trip_count": 3})
        try:
            assert convert_to_static(f)(10) == 10
        finally:
            set_flags({"FLAGS_dy2static_max_trip_count": 0})

    def test_non_range_for_left_alone(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(xs):
            s = 0.0
            for v in xs:
                s = s + v
            return s

        assert convert_to_static(f)([1.0, 2.0, 3.0]) == 6.0


class TestMaybeReturnRaises:
    """r4 advisor: a traced ret_flag with a dynamically-possible
    fall-through (implicit None) must raise, not return a joined tensor."""

    def test_fallthrough_maybe_return_raises(self):
        """Integration: the traced maybe-return surfaces a domain error (the
        value-structure mismatch between the returning and non-returning
        paths), not a raw jax TypeError or a silently wrong value."""
        from paddle_tpu.jit.dy2static import (
            DataDependentControlFlowError, convert_to_static)

        def f(x):
            i = paddle.to_tensor(0)
            while i < 5:
                if paddle.sum(x) * 0 + i == 3:   # traced return condition
                    return x * 2
                i = i + 1
            # NO trailing return: dynamic fall-through yields None

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x):
            return g(x)

        with pytest.raises(DataDependentControlFlowError):
            step(_t([1.0, 2.0]))

    def test_final_return_guard_unit(self):
        """Unit: final_return with a traced flag raises when static analysis
        could not prove every path returns (r4 advisor), and returns the
        joined value when it could."""
        import jax
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.jit.dy2static import (
            DataDependentControlFlowError, _JST)

        val = paddle.to_tensor([1.0])

        def run(a):
            flag = Tensor(a, _internal=True)
            with pytest.raises(DataDependentControlFlowError,
                               match="fall through"):
                _JST.final_return(flag, val, False)
            out = _JST.final_return(flag, val, True)
            assert out is val
            return a

        jax.eval_shape(run, jax.ShapeDtypeStruct((), np.bool_))

    def test_traced_inloop_return_raises_domain_error(self):
        """A return under a TRACED in-loop condition joins None with a
        Tensor (the not-returned path has no value) — the contract is a
        DataDependentControlFlowError with restructuring guidance, never a
        raw jax TypeError and never a silently wrong value. (Concrete
        in-loop returns work: TestEscapeConversion.test_return_in_loop.)"""
        from paddle_tpu.jit.dy2static import (
            DataDependentControlFlowError, convert_to_static)

        def f(x):
            i = paddle.to_tensor(0)
            while i < 5:
                if paddle.sum(x) * 0 + i == 3:
                    return x * 2
                i = i + 1
            return x * 10

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x):
            return g(x)

        with pytest.raises(DataDependentControlFlowError):
            step(_t([1.0, 2.0]))


class TestShadowedRange:
    """`_ForToWhileRewriter` resolves the NAME `range` against the
    function's locals/closure/globals and SKIPS the for->while rewrite when
    it is shadowed (ADVICE round-5 finding): a user's own `range` must run
    with its own semantics as a plain Python loop, never be silently
    lowered to builtin-range counter arithmetic."""

    def test_closure_shadow_keeps_user_semantics(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def custom_range(n):
            return [10, 20]          # 2 iterations whatever n says

        def make():
            range = custom_range     # noqa: A001 — the shadow under test

            def f(x):
                acc = x * 0
                for i in range(5):
                    acc = acc + i
                return acc
            return f

        f = make()
        out = convert_to_static(f)(_t([1.0]))
        # builtin semantics would yield 0+1+2+3+4 = 10; the user's range
        # yields 10+20 = 30
        np.testing.assert_allclose(np.asarray(out._data), [30.0])

    def test_local_assignment_shadow_skips_rewrite(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x):
            range = lambda n: [7]    # noqa: A001, E731 — local shadow
            acc = x * 0
            for i in range(3):
                acc = acc + i
            return acc

        out = convert_to_static(f)(_t([1.0]))
        np.testing.assert_allclose(np.asarray(out._data), [7.0])

    def test_param_shadow_skips_rewrite(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x, range):             # noqa: A002 — parameter shadow
            acc = x * 0
            for i in range(2):
                acc = acc + i
            return acc

        out = convert_to_static(f)(_t([1.0]), lambda n: [5, 6])
        np.testing.assert_allclose(np.asarray(out._data), [11.0])

    def test_nested_def_shadow_scoped_correctly(self):
        """A `range` shadow LOCAL to a nested def must stop the rewrite for
        that def's loops only — the enclosing function's own loops still
        convert; and the nested scope's loop runs the user's iterable."""
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x):
            def inner(y):
                range = lambda n: [7]    # noqa: A001, E731
                acc = y * 0
                for i in range(3):       # user's range: one iteration of 7
                    acc = acc + i
                return acc

            out = inner(x)
            for j in range(2):           # builtin: 0 + 1
                out = out + j
            return out

        got = convert_to_static(f)(_t([1.0]))
        np.testing.assert_allclose(np.asarray(got._data), [8.0])

    def test_nested_import_shadow_skips_rewrite(self):
        """Import bindings shadow too: `from operator import itemgetter as
        range` in a nested def must stop the rewrite for that scope."""
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x):
            def inner(y):
                from operator import itemgetter as range  # noqa: A004
                acc = y * 0
                for i in range(0):           # itemgetter(0): NOT iterable —
                    pass                     # but builtin range(0) would
                return acc                   # loop zero times, no raise
            try:
                inner(x)
            except TypeError:                # user semantics preserved:
                return x + 1                 # int is not iterable
            raise AssertionError("import shadow was rewritten away")

        got = convert_to_static(f)(_t([1.0]))
        np.testing.assert_allclose(np.asarray(got._data), [2.0])

    def test_global_shadow_skips_rewrite(self):
        from paddle_tpu.jit.dy2static import (_range_is_builtin,
                                              convert_to_static)

        glb = {"__builtins__": __builtins__,
               "range": lambda n: [100]}
        src = ("def f(x):\n"
               "    acc = x * 0\n"
               "    for i in range(4):\n"
               "        acc = acc + i\n"
               "    return acc\n")
        ns = {}
        exec(compile(src, "<test_global_shadow>", "exec"), glb, ns)
        import ast
        import inspect
        import textwrap
        f = ns["f"]
        assert not _range_is_builtin(f, ast.parse(src).body[0])
        # source for exec'd fns is unavailable; assert the resolver alone
        # (convert_to_static needs inspect.getsource) — plus the builtin
        # direction on a real function:

        def g(x):
            acc = x * 0
            for i in range(3):
                acc = acc + i
            return acc

        assert _range_is_builtin(
            g, ast.parse(textwrap.dedent(inspect.getsource(g))).body[0])
        out = convert_to_static(g)(_t([1.0]))
        np.testing.assert_allclose(np.asarray(out._data), [3.0])

    def test_builtin_range_still_converts_traced_bound(self):
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x, n):
            s = x * 0.0
            for i in range(n):
                s = s + 1
            return s

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x, n):
            return g(x, n)

        got = float(step(_t(1.0), paddle.to_tensor(4)))
        np.testing.assert_allclose(got, 4.0)

    def test_class_attr_range_is_not_a_function_shadow(self):
        """A class-body `range = ...` binds in the CLASS scope, not the
        enclosing function's — the function's loops must still convert."""
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x, n):
            class Meta:              # noqa: A003 — class scope only
                range = (1, 2)
            s = x * 0.0 + Meta.range[0] - 1
            for i in range(n):       # builtin range is still in effect
                s = s + 1
            return s

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x, n):
            return g(x, n)

        got = float(step(_t(1.0), paddle.to_tensor(4)))
        np.testing.assert_allclose(got, 4.0)

    def test_comprehension_target_range_is_not_a_shadow(self):
        """A comprehension target named `range` lives in the
        comprehension's own scope (py3) — no function-scope shadow."""
        from paddle_tpu.jit.dy2static import convert_to_static

        def f(x, n):
            pairs = [range * 0 for range in (1, 2)]  # noqa: A001
            s = x * 0.0 + pairs[0]
            for i in range(n):
                s = s + 1
            return s

        g = convert_to_static(f)

        @paddle.jit.to_static
        def step(x, n):
            return g(x, n)

        got = float(step(_t(1.0), paddle.to_tensor(4)))
        np.testing.assert_allclose(got, 4.0)
