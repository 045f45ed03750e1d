"""Multi-tenant QoS in the decode engine (ISSUE 16): preempt-to-host
token identity (greedy, seeded, speculative), chaos-abandoned
preemption isolation, weighted-fair admission, quota deferral, and the
seeded scenario harness's determinism + replay bookkeeping."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.decode import DecodeEngine, SpecDecodeEngine
from paddle_tpu.inference.errors import (ERR_RESOURCE_EXHAUSTED,
                                         TypedServeError)
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_tiny
from paddle_tpu.observability import REGISTRY
from paddle_tpu.testing import chaos


@pytest.fixture(scope="module")
def gpt_models():
    paddle.seed(7)
    return {
        "tiny": GPT(gpt_tiny()),
        "draft": GPT(GPTConfig(vocab_size=512, max_seq_len=128, hidden=32,
                               layers=1, heads=2, scan_layers=False)),
    }


def _full_logits(model, toks):
    idx = paddle.to_tensor(np.asarray([toks], np.int64))
    return model(idx).numpy()[0, -1].astype(np.float32)


def _ref_greedy(model, prompt, n):
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        t = int(_full_logits(model, toks).argmax())
        out.append(t)
        toks.append(t)
    return out


def _drain_events(stream, timeout=120.0):
    """Collect every token event plus the done payload off one stream:
    ``(streamed_tokens, done_tokens)``."""
    streamed = []
    while True:
        ev = stream.next_event(timeout=timeout)
        if ev[0] == "done":
            return streamed, ev[1]
        streamed.append(ev[1])


def _wait_tokens(stream, n, timeout=60.0):
    """Poll until the stream has emitted >= n token events; returns the
    tokens seen so far (the stream stays live)."""
    seen = []
    deadline = time.monotonic() + timeout
    while len(seen) < n and time.monotonic() < deadline:
        ev = stream.poll()
        if ev is None:
            time.sleep(0.005)
            continue
        assert ev[0] == "token", ev
        seen.append(ev[1])
    assert len(seen) >= n, f"only {len(seen)} tokens before timeout"
    return seen


def _flat(*names):
    flat = REGISTRY.flat()
    return {n: flat.get(n, 0.0) for n in names}


# -- preempt-to-host / resume: token identity ----------------------------

def test_preempt_resume_token_identity_greedy(gpt_models):
    """A preempted-then-resumed greedy stream is token-identical to an
    unpreempted run, and the client-facing stream is gapless: streamed
    token events equal the final done payload exactly."""
    model = gpt_models["tiny"]
    rng = np.random.RandomState(41)
    p_vic = rng.randint(0, 512, size=9)
    p_hi = rng.randint(0, 512, size=7)
    ref_vic = _ref_greedy(model, p_vic, 16)
    ref_hi = _ref_greedy(model, p_hi, 6)
    eng = DecodeEngine(model, max_slots=1, max_new_tokens=16,
                       page_tokens=4, preempt=True)
    try:
        m0 = _flat("paddle_tpu_decode_preemptions_total",
                   "paddle_tpu_decode_preempt_resumes_total")
        vic = eng.submit(p_vic, max_new_tokens=16)
        early = _wait_tokens(vic, 3)       # mid-generation, not at start
        hi = eng.submit(p_hi, max_new_tokens=6, priority=5)
        streamed_hi, done_hi = _drain_events(hi)
        assert done_hi == ref_hi
        assert streamed_hi == done_hi
        streamed_vic, done_vic = _drain_events(vic)
        assert done_vic == ref_vic, \
            "resumed stream diverged from the unpreempted reference"
        assert early + streamed_vic == done_vic, \
            "stream re-emitted or dropped tokens across preemption"
        m1 = _flat("paddle_tpu_decode_preemptions_total",
                   "paddle_tpu_decode_preempt_resumes_total")
        assert m1["paddle_tpu_decode_preemptions_total"] \
            > m0["paddle_tpu_decode_preemptions_total"]
        assert m1["paddle_tpu_decode_preempt_resumes_total"] \
            > m0["paddle_tpu_decode_preempt_resumes_total"]
    finally:
        eng.stop()


def test_preempt_resume_token_identity_seeded(gpt_models):
    """Same contract under temperature sampling: the per-(seed,
    position) RNG makes a resumed stream draw the same tokens it would
    have drawn uncontended."""
    model = gpt_models["tiny"]
    rng = np.random.RandomState(43)
    p_vic = rng.randint(0, 512, size=8)
    p_hi = rng.randint(0, 512, size=6)
    ref_eng = DecodeEngine(model, max_slots=1, max_new_tokens=16,
                           page_tokens=4, preempt=False)
    try:
        ref = ref_eng.submit(p_vic, max_new_tokens=14, temperature=0.8,
                             seed=123).result(timeout=120)
    finally:
        ref_eng.stop()
    eng = DecodeEngine(model, max_slots=1, max_new_tokens=16,
                       page_tokens=4, preempt=True)
    try:
        m0 = _flat("paddle_tpu_decode_preemptions_total")
        vic = eng.submit(p_vic, max_new_tokens=14, temperature=0.8,
                         seed=123)
        _wait_tokens(vic, 3)
        hi = eng.submit(p_hi, max_new_tokens=5, priority=5)
        hi.result(timeout=120)
        assert vic.result(timeout=120) == ref, \
            "seeded resumed stream diverged from the unpreempted run"
        assert _flat("paddle_tpu_decode_preemptions_total")[
            "paddle_tpu_decode_preemptions_total"] \
            > m0["paddle_tpu_decode_preemptions_total"]
    finally:
        eng.stop()


def test_preempt_resume_token_identity_speculative(gpt_models):
    """Preemption composes with draft-and-verify: a preempted spec
    stream still matches the full-forward greedy reference."""
    model = gpt_models["tiny"]
    rng = np.random.RandomState(47)
    p_vic = rng.randint(0, 512, size=8)
    p_hi = rng.randint(0, 512, size=6)
    ref_vic = _ref_greedy(model, p_vic, 12)
    ref_hi = _ref_greedy(model, p_hi, 5)
    eng = SpecDecodeEngine(model, draft_model=gpt_models["draft"],
                           speculate_k=4, max_slots=1, max_new_tokens=16,
                           page_tokens=4, preempt=True)
    try:
        m0 = _flat("paddle_tpu_decode_preemptions_total")
        vic = eng.submit(p_vic, max_new_tokens=12)
        _wait_tokens(vic, 2)
        hi = eng.submit(p_hi, max_new_tokens=5, priority=5)
        assert hi.result(timeout=120) == ref_hi
        assert vic.result(timeout=120) == ref_vic
        assert _flat("paddle_tpu_decode_preemptions_total")[
            "paddle_tpu_decode_preemptions_total"] \
            > m0["paddle_tpu_decode_preemptions_total"]
    finally:
        eng.stop()


def test_preempt_chaos_abandons_eviction_victim_unharmed(gpt_models):
    """Chaos at decode.preempt abandons the eviction: the victim keeps
    its slot and decodes to the correct answer, the high-priority
    candidate is requeued (served after, not dropped), and no
    preemption is counted."""
    model = gpt_models["tiny"]
    rng = np.random.RandomState(53)
    p_vic = rng.randint(0, 512, size=8)
    p_hi = rng.randint(0, 512, size=6)
    ref_vic = _ref_greedy(model, p_vic, 12)
    ref_hi = _ref_greedy(model, p_hi, 5)
    eng = DecodeEngine(model, max_slots=1, max_new_tokens=16,
                       page_tokens=4, preempt=True)
    try:
        m0 = _flat("paddle_tpu_decode_preemptions_total")
        with chaos.inject("decode.preempt:1+:RuntimeError") as sched:
            vic = eng.submit(p_vic, max_new_tokens=12)
            _wait_tokens(vic, 3)
            hi = eng.submit(p_hi, max_new_tokens=5, priority=5)
            assert vic.result(timeout=120) == ref_vic, \
                "abandoned preemption corrupted the victim"
            assert hi.result(timeout=120) == ref_hi, \
                "requeued candidate was dropped or corrupted"
        assert sched.fired, "decode.preempt site never armed"
        assert _flat("paddle_tpu_decode_preemptions_total")[
            "paddle_tpu_decode_preemptions_total"] \
            == m0["paddle_tpu_decode_preemptions_total"]
    finally:
        eng.stop()


# -- weighted-fair admission and quota -----------------------------------

def test_weighted_fair_admission_ratio(gpt_models):
    """With both tenants backlogged behind one slot, a 4x-weighted
    tenant wins the clear majority of early admissions even though the
    light tenant enqueued first."""
    model = gpt_models["tiny"]
    rng = np.random.RandomState(59)
    eng = DecodeEngine(model, max_slots=1, max_new_tokens=8,
                       max_pending=64, tenant_weights="heavy:4,light:1")
    try:
        blocker = eng.submit(rng.randint(0, 512, size=6),
                             max_new_tokens=8)
        light = [eng.submit(rng.randint(0, 512, size=5),
                            max_new_tokens=2, tenant="light")
                 for _ in range(10)]
        heavy = [eng.submit(rng.randint(0, 512, size=5),
                            max_new_tokens=2, tenant="heavy")
                 for _ in range(10)]
        blocker.result(timeout=120)
        open_streams = {("light", i): s for i, s in enumerate(light)}
        open_streams.update({("heavy", i): s for i, s in enumerate(heavy)})
        order = []
        deadline = time.monotonic() + 120
        while open_streams and time.monotonic() < deadline:
            moved = False
            for key in list(open_streams):
                ev = open_streams[key].poll()
                if ev is None:
                    continue
                moved = True
                if ev[0] == "done":
                    order.append(key[0])
                    del open_streams[key]
            if not moved:
                time.sleep(0.002)
        assert not open_streams, "streams still open at deadline"
        n_heavy_early = order[:10].count("heavy")
        assert n_heavy_early >= 6, \
            f"weighted-fair admission broke: first 10 finishers were " \
            f"{order[:10]}"
    finally:
        eng.stop()


def test_quota_deferral_queues_never_drops(gpt_models):
    """A tenant past its token-rate quota is deferred (queued), never
    shed: every request completes correctly, and the deferral is
    counted."""
    model = gpt_models["tiny"]
    rng = np.random.RandomState(61)
    prompts = [rng.randint(0, 512, size=6) for _ in range(5)]
    refs = [_ref_greedy(model, p, 4) for p in prompts]
    eng = DecodeEngine(model, max_slots=2, max_new_tokens=8,
                       max_pending=64, tenant_quota="capped:8")
    try:
        # compile the shapes the scenario uses first, under a tenant
        # that has no quota: the bucket refills by wall time (8 tokens a
        # second), so a run that compiles its steps as it goes (seconds
        # each on a cold cache) never falls into debt and nothing is
        # deferred: the test then failed by the machine's speed
        for s in [eng.submit(p, max_new_tokens=4, tenant="free")
                  for p in prompts[:2]]:
            s.result(timeout=120)
        m0 = _flat('paddle_tpu_tenant_quota_deferred_total'
                   '{tenant="capped"}')
        streams = [eng.submit(p, max_new_tokens=4, tenant="capped")
                   for p in prompts]
        free = eng.submit(prompts[0], max_new_tokens=4, tenant="free")
        assert free.result(timeout=120) == refs[0]
        for s, ref in zip(streams, refs):
            assert s.result(timeout=120) == ref
        m1 = _flat('paddle_tpu_tenant_quota_deferred_total'
                   '{tenant="capped"}')
        assert m1['paddle_tpu_tenant_quota_deferred_total'
                  '{tenant="capped"}'] \
            > m0['paddle_tpu_tenant_quota_deferred_total'
                 '{tenant="capped"}'], \
            "quota never deferred the capped tenant"
    finally:
        eng.stop()


def test_tenant_share_shed_spares_other_tenants(gpt_models):
    """A flood filling its weighted share of the pending queue is shed
    with a typed RESOURCE_EXHAUSTED — while another tenant's submit
    still admits (the global watermark must not be floodable)."""
    model = gpt_models["tiny"]
    rng = np.random.RandomState(67)
    eng = DecodeEngine(model, max_slots=1, max_new_tokens=8,
                       max_pending=8, tenant_weights="good:4,flood:1")
    try:
        blocker = eng.submit(rng.randint(0, 512, size=6),
                             max_new_tokens=8)
        flood_streams, sheds = [], 0
        for _ in range(16):
            try:
                flood_streams.append(
                    eng.submit(rng.randint(0, 512, size=5),
                               max_new_tokens=2, tenant="flood"))
            except TypedServeError as e:
                assert e.code == ERR_RESOURCE_EXHAUSTED
                sheds += 1
        assert sheds > 0, "flood never hit its share"
        good = eng.submit(rng.randint(0, 512, size=5), max_new_tokens=2,
                          tenant="good")   # must NOT raise
        blocker.result(timeout=120)
        assert len(good.result(timeout=120)) == 2
        for s in flood_streams:
            s.result(timeout=120)
    finally:
        eng.stop()


# -- scenario harness: determinism and replay bookkeeping ----------------

def test_scenarios_deterministic_and_shaped():
    from benchmarks import scenarios
    for name in scenarios.SCENARIOS:
        a = scenarios.generate(name, seed=3, duration_s=2.0)
        b = scenarios.generate(name, seed=3, duration_s=2.0)
        assert a == b, f"{name} is not seed-deterministic"
        assert a != scenarios.generate(name, seed=4, duration_s=2.0)
        assert a, f"{name} generated no arrivals"
        assert all(a[i].t <= a[i + 1].t for i in range(len(a) - 1))
        assert len({arr.tenant for arr in a}) >= 2
    flood = scenarios.generate("adversarial_flood", seed=3,
                               duration_s=2.0, capacity_rps=8.0,
                               flood_factor=4.0)
    per = {}
    for arr in flood:
        per[arr.tenant] = per.get(arr.tenant, 0) + 1
    # the flood really floods: >= 4x the well-behaved tenant's rate
    assert per["flood"] >= 4 * per["tenant-a"]
    assert all(arr.priority == 1 for arr in flood
               if arr.tenant == "tenant-a")


class _StubStream:
    def __init__(self, toks):
        self._ev = [("token", t, False) for t in toks] + [("done", toks)]

    def poll(self):
        return self._ev.pop(0) if self._ev else None


class _StubEngine:
    """Sheds every second flood submit; serves everyone else."""

    def __init__(self):
        self.flood_seen = 0

    def submit(self, prompt, tenant=None, priority=None,
               max_new_tokens=None):
        if tenant == "flood":
            self.flood_seen += 1
            if self.flood_seen % 2 == 0:
                raise TypedServeError(ERR_RESOURCE_EXHAUSTED,
                                      "synthetic shed")
        return _StubStream(list(range(int(max_new_tokens))))


def test_replay_and_score_bookkeeping():
    from benchmarks import scenarios
    arrivals = scenarios.generate("adversarial_flood", seed=5,
                                  duration_s=2.0, capacity_rps=10.0)
    eng = _StubEngine()
    outcomes = scenarios.replay(eng, arrivals, timeout_s=30.0,
                                speedup=40.0)
    assert len(outcomes) == len(arrivals)
    verdict = scenarios.score(outcomes, duration_s=2.0)
    good, flood = verdict["tenant-a"], verdict["flood"]
    assert good["shed"] == 0 and good["lost"] == 0
    assert good["ok"] == good["submitted"]
    assert flood["shed"] == eng.flood_seen // 2
    assert flood["ok"] + flood["shed"] == flood["submitted"]
    assert flood["lost"] == flood["submitted"] - flood["ok"]
    n_tok = arrivals[0].max_new
    assert good["tokens"] == good["ok"] * n_tok
    assert good["goodput_tps"] == pytest.approx(
        good["tokens"] / 2.0, rel=1e-6)
    assert good["p99_ms"] >= good["p50_ms"] >= 0.0
