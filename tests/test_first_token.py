"""Admission without a readback (docs/SERVING.md "De-synchronized step
loop"): a prefill's first token stays on the chip, joins the token chain
there, and reaches the host through the in-flight fifo like every other
token.

The contract: the served tokens are the tokens the synchronous path
serves (block on the token inside admission, hand it back through the
upload's fresh flag), for every family of prefill; what the host token
used to decide inside admission (EOS, a budget of one, a cancel, a
deadline) is decided at the harvest; and `engine.admit` waits for
nothing.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
from paddle_tpu.observability import metrics

DEFERRED = "engine.first_tokens_deferred"
SYNC = "engine.first_tokens_sync"


def _tiny_model(seed=7, vocab=97, max_pos=64):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    return GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position_embeddings=max_pos,
        hidden_dropout=0.0, attention_dropout=0.0))


def _tiny_hybrid():
    from paddle_tpu.models import phi4flash as phi
    cfg = phi.tiny_config()
    return phi.Phi4FlashForCausalLM(cfg, phi.init_params(cfg, seed=7,
                                                         std=0.1))


def _prompt(n, seed, vocab=96):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


def _sync_first_token(self, slot, req, toks, t0):
    """Admission as it was before the first token stayed on the chip,
    from the engine's own two methods: block on the token, then seed the
    slot with it from the host."""
    self._seed_first_token(slot, req, self._read_first_token(toks, slot))


def _count(name):
    return metrics.counter(name).value


# each family of prefill: (model, EngineConfig fields, submit keywords,
# prompt lengths of a first wave and of a second one submitted after it)
GPT = dict(page_size=4, max_slots=2, min_bucket=8)
FAMILIES = {
    "greedy": (_tiny_model, GPT, {}, [5, 9, 3], [7]),
    "sampling-seeded": (_tiny_model, dict(GPT, sampling=True),
                        dict(temperature=0.8, top_k=5, seed=3),
                        [5, 9, 3], [7]),
    "int8-pool": (_tiny_model, dict(GPT, kv_dtype="int8"), {},
                  [5, 9, 3], [7]),
    "chunked-final-chunk": (_tiny_model, dict(GPT, prefill_chunk_tokens=8),
                            {}, [21, 5, 17], [9]),
    # the second wave repeats the first prompt's leading pages: a tail
    "prefix-cache-tail": (_tiny_model, dict(GPT, prefix_cache=True), {},
                          [13, 6], "tail"),
    "tiny-hybrid": (_tiny_hybrid,
                    dict(page_size=4, max_slots=3, max_seq_len=64,
                         prefill_chunk_tokens=8, prefix_cache=False), {},
                    [21, 5, 8], [11]),
}


def _serve(family, sync):
    """The family's two waves served to the end: each answer, and how
    many first tokens came through the fifo."""
    make, ecfg, submit_kw, first, second = FAMILIES[family]
    with pytest.MonkeyPatch.context() as patch:
        if sync:
            patch.setattr(DecodeEngine, "_first_token", _sync_first_token)
        eng = DecodeEngine(make(), EngineConfig(**ecfg))
        base = _count(DEFERRED)
        reqs = [eng.submit(_prompt(n, 20 + i), 6, **submit_kw)
                for i, n in enumerate(first)]
        eng.run_until_idle(max_steps=200)
        if second == "tail":
            later = [np.concatenate([_prompt(first[0], 20)[:8],
                                     _prompt(5, 40)])]
        else:
            later = [_prompt(n, 30 + i) for i, n in enumerate(second)]
        hits = _count("engine.prefix_hit")
        reqs += [eng.submit(p, 6, **submit_kw) for p in later]
        eng.run_until_idle(max_steps=200)
        if second == "tail":
            assert _count("engine.prefix_hit") == hits + 1
    return [list(r.result(timeout=30)) for r in reqs], _count(DEFERRED) - base


@pytest.mark.parametrize("family", FAMILIES)
def test_deferred_first_tokens_are_the_synchronous_ones(family):
    want, none = _serve(family, sync=True)
    got, deferred = _serve(family, sync=False)
    assert none == 0 and deferred == len(got)
    assert got == want


def _engine(**over):
    return DecodeEngine(_tiny_model(), EngineConfig(**dict(GPT, **over)))


def _ref(model, prompt, n):
    ids = paddle.Tensor(np.asarray(prompt)[None].astype(np.int32),
                        _internal=True)
    return list(np.asarray(model.fast_generate(ids, max_new_tokens=n)
                           .numpy())[0])


def test_first_token_eos_retires_at_harvest_and_drops_the_surplus_step():
    """The first token is EOS: nobody knows inside admission, one decode
    step runs for nothing, and the harvest delivers exactly the EOS."""
    m = _tiny_model()
    prompt = _prompt(5, 50)
    eos = _ref(m, prompt, 1)[-1]
    eng = DecodeEngine(m, EngineConfig(**dict(GPT, eos_id=int(eos),
                                              inflight=4)))
    steps = _count("engine.steps")
    req = eng.submit(prompt, 6)
    other = eng.submit(_prompt(6, 51), 4)
    eng.step()
    assert req.generated == [] and not req.done
    eng.run_until_idle(max_steps=50)
    assert req.generated == [eos] and req.done
    assert _count("engine.steps") > steps           # the surplus step ran
    # the slot and its pages are back, and its neighbour saw nothing
    want = _ref(m, _prompt(6, 51), 4)[6:]
    if eos in want:
        want = want[:want.index(eos) + 1]
    assert other.generated == want
    assert eng.allocator.free_pages == eng.allocator.num_pages - 1


def test_budget_of_one_token_never_dispatches_a_decode_step():
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(**GPT))
    steps = _count("engine.steps")
    prompt = _prompt(5, 52)
    req = eng.submit(prompt, 1)
    eng.run_until_idle(max_steps=10)
    assert list(req.result(timeout=30)) == _ref(m, prompt, 1)
    assert _count("engine.steps") == steps
    assert eng.allocator.free_pages == eng.allocator.num_pages - 1


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_request_dead_between_launch_and_harvest_delivers_nothing(how):
    """A cancel or a deadline that lands after the prefill launched and
    before its token was harvested: the reap retires the slot, and the
    fifo's entries for it (the first token, the surplus step) are
    dropped."""
    eng = _engine(max_slots=1, inflight=3)
    req = eng.submit(_prompt(5, 53), 6,
                     **({"deadline_s": 0.05} if how == "deadline" else {}))
    eng.step()                              # launch + dispatch, no harvest
    assert len(eng._inflight) == 2 and req.generated == []
    if how == "cancel":
        assert eng.cancel(req.request_id)
    else:
        time.sleep(0.06)
    nxt = eng.submit(_prompt(4, 54), 3)     # takes the slot over
    eng.run_until_idle(max_steps=50)
    with pytest.raises(RuntimeError, match="cancelled by client"
                       if how == "cancel" else "request deadline"):
        req.result(timeout=5)
    assert req.generated == []
    assert list(nxt.result(timeout=30)) == _ref(_tiny_model(),
                                                _prompt(4, 54), 3)
    assert eng.allocator.free_pages == eng.allocator.num_pages - 1


def test_two_admissions_in_one_step_each_ride_the_fifo():
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(**dict(GPT, inflight=4)))
    prompts = [_prompt(5, 55), _prompt(9, 56)]
    reqs = [eng.submit(p, 5) for p in prompts]
    eng.step()
    # two first-token entries, then the step that reads both off the chain
    assert [[s for s, _ in snap] for _, snap, _ in eng._inflight] \
        == [[0], [1], [0, 1]]
    assert all(r.generated == [] for r in reqs)
    eng.run_until_idle(max_steps=50)
    for r, p in zip(reqs, prompts):
        assert list(r.result(timeout=30)) == _ref(m, p, 5)


@pytest.mark.parametrize("inflight", [1, 2, 3])
def test_first_token_entries_count_toward_the_window(inflight):
    """After every `step()` that dispatched, the fifo holds
    ``inflight - 1`` entries at most: a first-token entry takes a place
    in the window as a step does."""
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(**dict(GPT, inflight=inflight)))
    prompts = [_prompt(5, 57), _prompt(7, 58), _prompt(3, 59)]
    reqs = [eng.submit(p, 6) for p in prompts]
    deepest = 0
    for _ in range(100):
        busy = eng.step()
        deepest = max(deepest, len(eng._inflight))
        assert len(eng._inflight) <= max(inflight - 1, 0)
        if not busy:
            break
    assert deepest == inflight - 1
    for r, p in zip(reqs, prompts):
        assert list(r.result(timeout=30)) == _ref(m, p, 6)


def test_speculating_engine_reads_its_first_token_inside_admission():
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(**dict(GPT, speculate_k=2)))
    base = _count(SYNC), _count(DEFERRED)
    prompt = _prompt(6, 60)
    req = eng.submit(prompt, 6)
    eng.step()
    assert len(req.generated) >= 1          # the host drafts from it
    eng.run_until_idle(max_steps=50)
    assert list(req.result(timeout=30)) == _ref(m, prompt, 6)
    assert (_count(SYNC) - base[0], _count(DEFERRED) - base[1]) == (1, 0)


def test_admission_holds_no_harvest_and_the_stamp_waits_for_the_host():
    """`engine.admit` blocks on nothing: the first token's readback is an
    `engine.harvest` (``of=prefill``) directly under `engine.step`, and
    the request's `t_first_token` is taken there, not at the launch."""
    eng = _engine(max_slots=1, inflight=3)
    t0 = time.perf_counter()
    req = eng.submit(_prompt(5, 61), 4)
    eng.step()
    assert req.trace.t_first_token is None and req.generated == []
    launched = time.perf_counter()
    eng.run_until_idle(max_steps=30)
    admits = {s.id for s in metrics.spans(name="engine.admit", since=t0)}
    steps = {s.id for s in metrics.spans(name="engine.step", since=t0)}
    harvests = metrics.spans(name="engine.harvest", since=t0)
    assert admits and harvests
    assert all(h.parent in steps and h.parent not in admits
               for h in harvests)
    assert [h.args["of"] for h in harvests] == ["prefill"] + ["decode"] * 3
    assert req.trace.t_first_token >= launched
