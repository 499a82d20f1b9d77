"""Traffic: the same seed gives the same schedule and tokens, every seed
the same sizes and arrivals in the same order with tokens of its own;
latency runs from the due time; shed, failed and
unfinished requests count as missing; the closed backlog never empties;
the calibration tokens are seeded."""
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.tpu import gen, spec, tiny  # noqa: E402
from benchmarks.tpu.jobs import serve  # noqa: E402
from repro.serving import engine as _engine  # noqa: E402,F401  (imported
# here so that the first submit inside a short window does not import it)

MIX = tiny.CHAT


def test_same_seed_same_schedule():
    a = gen.open_loop(MIX, 2 ** 31 + 17, 2.0, 10.0, 512, 5.0)
    b = gen.open_loop(MIX, 2 ** 31 + 17, 2.0, 10.0, 512, 5.0)
    assert [r.due for r in a] == [r.due for r in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_seeds_permute_one_pool():
    """Seeds send one pool in one order: the same work at the same times,
    with prompts of their own."""
    a = gen.open_loop(MIX, 1, 2.0, 10.0, 512, 5.0)
    b = gen.open_loop(MIX, 2, 2.0, 10.0, 512, 5.0)
    assert len(a) == len(b) == 60
    win = [r for r in a if r.due >= 2.0]
    assert len(win) == 50 and win[0].due == 2.0
    assert [r.due for r in a] == [r.due for r in b]
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in b]
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert len({(len(r.prompt), r.max_new) for r in win}) > 10
    assert all(len(r.prompt) in MIX["prompt_grid"] for r in a)
    assert max(r.due for r in a) < 12.0


def test_real_chat_mix_lengths():
    mix = spec.load_cell("serve.mistral-nemo-12b.chat").traffic
    p, o = gen.size_pool(mix, 2000)
    assert set(p) <= set(mix["prompt_grid"])
    assert 384 <= np.median(p) <= 768 and 96 <= np.median(o) <= 160
    assert o.min() >= 16 and o.max() <= 512


def _out(first, finish, n, status="ok"):
    return types.SimpleNamespace(first_token_time=first, finish_time=finish,
                                 tokens=[0] * n, status=status,
                                 finished_ok=status == "ok")


def test_latency_from_due_and_missing_requests():
    recs = [{"due": 10.0, "out": _out(10.5, 11.5, 11)},
            {"due": 10.2, "out": None, "status": "shed"},
            {"due": 10.4, "out": _out(11.0, 12.0, 3, "failed")},
            {"due": 10.6, "out": None}]                 # never finished
    ttft, tpot, failed = serve.latencies(recs, t_end=20.0)
    assert ttft[0] == pytest.approx(0.5) and tpot[0] == pytest.approx(100.0)
    assert failed == 3
    assert ttft[1:] == pytest.approx([9.8, 9.6, 9.4])


class FakeEngine:
    """Serves one queued request per round, one round per step."""

    def __init__(self):
        self.max_slots, self.queue, self.n, self.min_queued = 2, [], 0, 99

    def submit(self, req):
        self.queue.append(self.n)
        self.n += 1
        return self.n - 1

    def load(self):
        return {"queued": len(self.queue)}

    @property
    def busy(self):
        return bool(self.queue)

    def step(self):
        self.min_queued = min(self.min_queued, len(self.queue))
        rid = self.queue.pop(0)
        return [types.SimpleNamespace(request_id=rid)]


class FakeProbe:
    record = traced = False
    at_start = at_close = None

    def __init__(self, engine):
        self.engine = engine

    def step(self):
        return self.engine.step()


def _run(arrivals, tmp_path):
    cell = types.SimpleNamespace(traffic=dict(MIX, arrivals=arrivals),
                                 params={"backlog_per_slot": 2})
    return types.SimpleNamespace(cell=cell, trace=False)


def test_backlog_never_empties(tmp_path):
    import time

    eng = FakeEngine()
    reqs = gen.backlog(MIX, 3, 16, 512)
    t0 = time.time()
    recs, by_rid, _ = serve.drive(_run("backlog", tmp_path), eng,
                                  FakeProbe(eng), reqs, t0, t0, t0 + 0.2,
                                  _null)
    assert eng.min_queued >= 2 * eng.max_slots
    assert len(recs) > 16          # the pool is cycled, never exhausted


def test_open_loop_sends_when_due(tmp_path):
    import time

    eng = FakeEngine()
    reqs = gen.open_loop(MIX, 4, 0.0, 0.3, 512, 40.0)
    t0 = time.time()
    recs, _, late = serve.drive(_run("open", tmp_path), eng, FakeProbe(eng),
                                reqs, t0, t0, t0 + 0.5, _null)
    assert len(recs) == len(reqs)
    assert [r["due"] - t0 for r in recs] == pytest.approx(
        [r.due for r in reqs], abs=1e-6)
    assert max(late) < 0.25


class _null:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_calibration_tokens_are_seeded():
    import jax

    from benchmarks.tpu import weights

    a = gen.zipf_corpus(weights.leaf_key(2 ** 33 + 1, "calibration"), 2, 32,
                        512)
    b = gen.zipf_corpus(weights.leaf_key(2 ** 33 + 1, "calibration"), 2, 32,
                        512)
    c = gen.zipf_corpus(weights.leaf_key(5, "calibration"), 2, 32, 512)
    assert (a == b).all() and not (a == c).all()
    assert a.shape == (2, 32) and int(a.min()) >= 2 and int(a.max()) < 512
    del jax
