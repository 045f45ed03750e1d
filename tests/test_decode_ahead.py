"""The decode tick running ahead of the host: step k+1 is dispatched
before step k's tokens are read, the last token of every slot stays on
the device between steps, and what the host does to a tick runs beside
the device.

The oracle is twofold: the full forward pass (`_ref_greedy`, as in
tests/test_decode.py) and the same engine made to read every step
before it dispatches the next (`_run_ahead` off, the order the engine
had before): the streams have to be token for token the same, through
EOS in mid-stream (the one-step overrun that is dropped), `max_new`
ends, a prefix hit with tail feeding, a preempt-resume and batch-rung
changes.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference.decode import DecodeEngine
from paddle_tpu.inference.errors import ERR_UNAVAILABLE, TypedServeError
from paddle_tpu.models.gpt import GPT, gpt_tiny
from paddle_tpu.observability import REGISTRY
from paddle_tpu.observability.tracez import RING

TIMEOUT = 180.0


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return GPT(gpt_tiny())


def _ref_greedy(model, prompt, n, eos_id=None):
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        idx = paddle.to_tensor(np.asarray([toks], np.int64))
        t = int(model(idx).numpy()[0, -1].astype(np.float32).argmax())
        out.append(t)
        toks.append(t)
        if eos_id is not None and t == eos_id:
            break
    return out


def _prompt_with_eos(model, rng, size):
    """(prompt, eos): a prompt whose greedy stream emits, as its third,
    fourth or fifth token, a token it has not emitted before: as
    `eos_id` that token ends the stream there, in mid-stream."""
    while True:
        prompt = rng.randint(0, 512, size=size)
        free = _ref_greedy(model, prompt, 5)
        for i, t in enumerate(free):
            if i >= 2 and t not in free[:i]:
                return prompt, t


def _engine(model, ahead=True, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_tokens", 4)
    kw.setdefault("max_new_tokens", 32)
    eng = DecodeEngine(model, **kw)
    eng._run_ahead = ahead      # off: every step is read before the next
    return eng


def _wait_tokens(stream, n):
    deadline = time.monotonic() + TIMEOUT
    while len(stream.tokens) < n:
        assert time.monotonic() < deadline, "stream stalled"
        time.sleep(0.002)


def _tick_spans(tid, t_from=0.0):
    """The engine thread's ring: {name: [(start, end, args)]}."""
    spans = {}
    for ph, name, ts, dur, etid, args in RING.snapshot()[0]:
        if etid == tid and ts >= t_from:
            spans.setdefault(name, []).append((ts, ts + dur, args or {}))
    return spans


def _mix(model, ahead):
    """One scripted churn; returns ({name: tokens}, stats, preemptions)."""
    rng = np.random.RandomState(3)
    p_eos, eos = _prompt_with_eos(model, rng, 9)
    p_len = rng.randint(0, 512, size=6)
    p_shared = rng.randint(0, 512, size=16)          # four whole pages
    p_hit = np.concatenate([p_shared[:12], rng.randint(0, 512, size=5)])
    p_v1, p_v2, p_hi = (rng.randint(0, 512, size=n) for n in (7, 10, 5))
    eng = _engine(model, ahead, preempt=True)
    pre0 = REGISTRY.flat().get("paddle_tpu_decode_preemptions_total", 0)
    out = {}
    try:
        # EOS in mid-stream beside a stream that ends by its count: the
        # batch rung goes 1 -> 2 -> 1
        s_eos = eng.submit(p_eos, max_new_tokens=12, eos_id=eos)
        s_len = eng.submit(p_len, max_new_tokens=7)
        out["eos"] = s_eos.result(timeout=TIMEOUT)
        out["len"] = s_len.result(timeout=TIMEOUT)
        # a prefix hit whose tail rides the step
        out["shared"] = eng.submit(p_shared,
                                   max_new_tokens=3).result(timeout=TIMEOUT)
        hits0 = REGISTRY.flat().get("paddle_tpu_decode_prefix_hits_total", 0)
        out["hit"] = eng.submit(p_hit,
                                max_new_tokens=6).result(timeout=TIMEOUT)
        assert REGISTRY.flat()["paddle_tpu_decode_prefix_hits_total"] > hits0
        # both slots busy, then a request that outranks them
        s_v1 = eng.submit(p_v1, max_new_tokens=30)
        s_v2 = eng.submit(p_v2, max_new_tokens=30)
        _wait_tokens(s_v2, 3)
        s_hi = eng.submit(p_hi, max_new_tokens=6, priority=5)
        out["hi"] = s_hi.result(timeout=TIMEOUT)
        out["v1"] = s_v1.result(timeout=TIMEOUT)
        out["v2"] = s_v2.result(timeout=TIMEOUT)
        stats = eng.stats()
    finally:
        eng.stop()
    pre = REGISTRY.flat().get("paddle_tpu_decode_preemptions_total", 0) - pre0
    want = {"eos": _ref_greedy(model, p_eos, 12, eos_id=eos),
            "len": _ref_greedy(model, p_len, 7),
            "shared": _ref_greedy(model, p_shared, 3),
            "hit": _ref_greedy(model, p_hit, 6),
            "hi": _ref_greedy(model, p_hi, 6),
            "v1": _ref_greedy(model, p_v1, 30),
            "v2": _ref_greedy(model, p_v2, 30)}
    return out, want, stats, pre


@pytest.fixture(scope="module")
def mixes(model):
    return {ahead: _mix(model, ahead) for ahead in (True, False)}


@pytest.mark.parametrize("name", ["eos", "len", "shared", "hit", "hi",
                                  "v1", "v2"])
def test_streams_running_ahead_are_those_of_reading_first(mixes, name):
    """(a) token for token: ahead == read-first == the full forward."""
    ahead, want, _, _ = mixes[True]
    first, _, _, _ = mixes[False]
    assert ahead[name] == first[name] == want[name]
    if name == "eos":
        assert 3 <= len(want[name]) <= 5     # it did end in mid-stream


def test_the_mix_ran_ahead_and_preempted(mixes):
    _, _, stats, preempted = mixes[True]
    assert preempted >= 1
    assert stats["ahead_steps"] > 0.5 * stats["steps"]
    assert stats["pages"]["pages_used"] \
        == stats["prefix_cache"]["cached_pages"]     # nothing leaked
    _, _, stats, preempted = mixes[False]
    assert preempted >= 1 and stats["ahead_steps"] == 0


def test_pages_freed_by_eos_are_reused_while_the_overrun_runs(model):
    """(b) a stream that ends by EOS is found out while the step that
    still holds its row runs; its pages go to the next admission at
    once (the pool here has no others to give) and both the survivor
    and the newcomer stay correct."""
    rng = np.random.RandomState(11)
    p_long, p_new = (rng.randint(0, 512, size=n) for n in (6, 10))
    p_eos, eos = _prompt_with_eos(model, rng, 8)
    # 7 pages for the survivor (26 rows), at most 4 for the one that
    # ends (8 + 5 rows and its overrun): the newcomer needs 4
    eng = _engine(model, num_pages=12, prefix_cache=False)
    try:
        s_long = eng.submit(p_long, max_new_tokens=20)
        s_eos = eng.submit(p_eos, max_new_tokens=12, eos_id=eos)
        assert s_eos.result(timeout=TIMEOUT) == _ref_greedy(
            model, p_eos, 12, eos_id=eos)
        s_new = eng.submit(p_new, max_new_tokens=4)
        assert s_new.result(timeout=TIMEOUT) == _ref_greedy(model, p_new, 4)
        assert s_long.result(timeout=TIMEOUT) == _ref_greedy(
            model, p_long, 20)
        st = eng.stats()
        assert st["pages"]["pages_used"] == 0 and st["ahead_steps"] > 0
    finally:
        eng.stop()


def test_a_sampling_row_makes_its_ticks_read_first(model):
    """(c) the host samples from [B, V] with the per-(seed, position)
    generator, so a tick that holds a sampling row is not dispatched
    ahead, its admission pulls its logits, and its seeded tokens are
    those of the engine that never runs ahead; the greedy stream beside
    it runs ahead again once it is alone."""
    rng = np.random.RandomState(5)
    p_greedy, p_sampled = rng.randint(0, 512, size=7), \
        rng.randint(0, 512, size=9)

    def run(ahead):
        eng = _engine(model, ahead)
        try:
            eng.warmup()
            tid, t0 = eng._thread.ident, time.perf_counter()
            g = eng.submit(p_greedy, max_new_tokens=30)
            _wait_tokens(g, 2)
            s = eng.submit(p_sampled, max_new_tokens=8, temperature=0.8,
                           top_k=20, seed=123)
            return (g.result(timeout=TIMEOUT), s.result(timeout=TIMEOUT),
                    s.request_id, _tick_spans(tid, t0))
        finally:
            eng.stop()

    greedy, sampled, rid, spans = run(True)
    assert (greedy, sampled) == run(False)[:2]
    assert greedy == _ref_greedy(model, p_greedy, 30)
    emits = [ts for ts, _, args in spans["decode.emit"]
             if args.get("req") == rid]
    assert len(emits) == 8
    during = [args["ahead"] for ts, _, args in spans["decode.step"]
              if emits[0] <= ts <= emits[-1]]
    assert len(during) >= 7 and not any(during)
    after = [args["ahead"] for ts, _, args in spans["decode.step"]
             if ts > emits[-1]]
    assert len(after) >= 10 and sum(after) >= len(after) - 1
    (adm,) = [a for a in spans["decode.admit"] if a[2].get("req") == rid]
    assert [name for name in ("decode.admit.logits_pull",
                              "decode.admit.emit", "exec:decode.pfirst")
            if any(adm[0] <= c[0] and c[1] <= adm[1]
                   for c in spans.get(name, []))] \
        == ["decode.admit.logits_pull", "decode.admit.emit"]


def test_ring_order_and_no_compile_after_warmup(model):
    """(d) step k+1's `exec:decode.pstep` begins before the
    `decode.step.pull` that reads step k, `decode.step.wait` comes
    between a tick and the next dispatch, and nearly every step of a
    greedy run is dispatched ahead; (e) nothing compiles after
    `warmup()`, the three token programs included."""
    rng = np.random.RandomState(9)
    eng = _engine(model, max_slots=3, max_new_tokens=64)
    try:
        eng.warmup()
        for cache, key in ((eng._tok_aot, "ptok"), (eng._pick_aot, "ppick")):
            assert sorted(cache.keys()) == [(key, b)
                                            for b in eng.batch_ladder]
        assert eng._first_aot.keys() == [("pfirst",)]
        compiled = len(profiler.compile_events())
        tid, t0 = eng._thread.ident, time.perf_counter()
        steps0 = eng.stats()["steps"]
        streams = [eng.submit(rng.randint(0, 512, size=n), max_new_tokens=m)
                   for n, m in ((5, 40), (9, 50), (6, 60))]
        for s, (_, m) in zip(streams, ((5, 40), (9, 50), (6, 60))):
            assert len(s.result(timeout=TIMEOUT)) == m
        st = eng.stats()
    finally:
        eng.stop()
    assert len(profiler.compile_events()) == compiled
    steps = st["steps"] - steps0
    assert steps >= 59 and st["ahead_steps"] > 0.9 * steps
    spans = _tick_spans(tid, t0)
    ticks = sorted(spans["decode.step"])
    assert len(ticks) == steps

    def first_inside(name, tick):
        return min(c[0] for c in spans[name]
                   if tick[0] <= c[0] and c[1] <= tick[1])

    for tick in ticks[1:]:
        assert tick[2]["ahead"] is True
        assert first_inside("exec:decode.pstep", tick) \
            < first_inside("decode.step.pull", tick)
    # step k+1 goes out, step k is read (the pull of the same tick),
    # the loop waits for step k+1, and only then does step k+2 go out
    steps_at = sorted(c[0] for c in spans["exec:decode.pstep"])
    pulls = sorted(c[0] for c in spans["decode.step.pull"])
    waits = sorted(c[0] for c in spans["decode.step.wait"])
    assert len(waits) == steps
    assert all(a < p < w < b for a, p, w, b in
               zip(steps_at, pulls, waits, steps_at[1:]))
    # no wait lies inside a tick: the loop admits between the two
    assert not any(t[0] <= w <= t[1] for t in ticks for w in waits)
    exec_ms = [1e3 * (c[1] - c[0]) for c in spans["exec:decode.pstep"]]
    wait_ms = [1e3 * (c[1] - c[0]) for c in spans["decode.step.wait"]]
    # an `exec:` event of the tick is the dispatch call alone: the
    # device's time is under the wait
    assert sum(exec_ms) < sum(wait_ms)


# (f) what brings the step in flight home

def _nothing_in_flight(eng):
    return eng._flight is None and not eng._firsts


def test_stop_leaves_no_step_in_flight(model):
    eng = _engine(model)
    try:
        s = eng.submit(np.arange(1, 8), max_new_tokens=30)
        _wait_tokens(s, 3)
    finally:
        eng.stop()
    assert _nothing_in_flight(eng)
    with pytest.raises(TypedServeError) as ei:
        s.result(timeout=TIMEOUT)
    assert ei.value.code == ERR_UNAVAILABLE


def test_preemption_brings_the_step_home_first(model):
    rng = np.random.RandomState(13)
    p_vic, p_hi = rng.randint(0, 512, size=9), rng.randint(0, 512, size=7)
    eng = _engine(model, max_slots=1, preempt=True)
    seen = []
    stash = eng._preempt_stash
    eng._preempt_stash = lambda req: (
        seen.append((_nothing_in_flight(eng), req.pending,
                     len(req.generated))), stash(req))[1]
    try:
        vic = eng.submit(p_vic, max_new_tokens=16)
        _wait_tokens(vic, 3)
        hi = eng.submit(p_hi, max_new_tokens=6, priority=5)
        assert hi.result(timeout=TIMEOUT) == _ref_greedy(model, p_hi, 6)
        assert vic.result(timeout=TIMEOUT) == _ref_greedy(model, p_vic, 16)
    finally:
        eng.stop()
    assert seen and all(home and pending == 0 and got >= 3
                        for home, pending, got in seen)


def test_handoff_export_brings_the_step_home_first(model):
    rng = np.random.RandomState(17)
    p_run, p_exp = rng.randint(0, 512, size=6), rng.randint(0, 512, size=12)
    eng = _engine(model, handoff=True)
    seen = []
    export = eng._export_kv
    eng._export_kv = lambda toks: (seen.append(_nothing_in_flight(eng)),
                                   export(toks))[1]
    try:
        s = eng.submit(p_run, max_new_tokens=30)
        _wait_tokens(s, 3)
        payload = eng.export_kv(p_exp)
        assert payload["n_pages"] == 3
        assert s.result(timeout=TIMEOUT) == _ref_greedy(model, p_run, 30)
    finally:
        eng.stop()
    assert seen == [True]


def test_the_error_path_leaves_no_step_in_flight(model):
    rng = np.random.RandomState(19)
    p1, p2 = rng.randint(0, 512, size=6), rng.randint(0, 512, size=8)
    eng = _engine(model)
    build, calls = eng._build_batch, []

    def failing():
        calls.append(_nothing_in_flight(eng))
        if len(calls) == 4:
            raise RuntimeError("scripted failure")
        return build()

    eng._build_batch = failing
    try:
        s = eng.submit(p1, max_new_tokens=30)
        with pytest.raises(TypedServeError) as ei:
            s.result(timeout=TIMEOUT)
        assert ei.value.code == ERR_UNAVAILABLE
        assert "scripted failure" in str(ei.value)
        assert calls[3] is False         # a step was in flight as it failed
        deadline = time.monotonic() + TIMEOUT
        while eng.stats()["active"] and time.monotonic() < deadline:
            time.sleep(0.002)
        assert _nothing_in_flight(eng)
        assert eng.stats()["pages"]["pages_used"] \
            == eng.stats()["prefix_cache"]["cached_pages"]
        # and it serves the next request
        assert eng.submit(p2, max_new_tokens=5).result(timeout=TIMEOUT) \
            == _ref_greedy(model, p2, 5)
    finally:
        eng.stop()
