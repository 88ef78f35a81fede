"""The traffic generator and the serving loop's clock, without a model."""

import time

import numpy as np

from benchmark.harness import serve, traffic
from benchmark.harness.trace import Tracer

MIX = {"prompt_len": {"dist": "loguniform", "lo": 32, "hi": 512},
       "output_len": {"dist": "loguniform", "lo": 32, "hi": 384}}


def test_same_seed_same_schedule_and_any_seed_same_work():
    big = 2**31 + 12345
    a = traffic.poisson_dues(4.0, big, 30.0)
    assert np.array_equal(a, traffic.poisson_dues(4.0, big, 30.0))
    b = traffic.poisson_dues(4.0, 7, 30.0)
    assert len(a) == len(b) == 120 and not np.array_equal(a, b)
    # the same gaps in another order (the first due time is half its own gap)
    assert np.allclose(np.sort(np.diff(a, prepend=-a[0])),
                       np.sort(np.diff(b, prepend=-b[0])))
    assert 0 <= a[0] and a[-1] < 30.0 and np.all(np.diff(a) > 0)
    ra = traffic.requests(MIX, big, 120, 50304, 1024)
    rb = traffic.requests(MIX, 7, 120, 50304, 1024)
    assert ra == traffic.requests(MIX, big, 120, 50304, 1024)
    assert sorted(len(r["prompt"]) for r in ra) == \
        sorted(len(r["prompt"]) for r in rb)
    assert sorted(r["out_len"] for r in ra) == sorted(r["out_len"] for r in rb)
    assert all(32 <= len(r["prompt"]) <= 512 and 32 <= r["out_len"] <= 384
               for r in ra)
    assert [r["prompt"] for r in ra] != [r["prompt"] for r in rb]
    # the window holds the same requests whatever the seed: ramp and window
    # are drawn apart
    for seed in (big, 7):
        recs = serve.open_recs(MIX, 0.75, seed, 8.0, 45.0, 50304, 1024)
        inside = [r for r in recs if 8.0 <= r.due < 53.0]
        assert len(recs) == 40 and len(inside) == 34
        assert sorted(len(r.prompt) for r in inside) == sorted(
            len(r["prompt"]) for r in traffic.requests(MIX, 1, 34, 50304, 1024))
    assert len(serve.open_recs(MIX, 2.0, 7, 0.0, 15.0, 50304, 1024)) == 30


def test_a_cycle_sends_every_seed_the_same_round_at_the_same_times():
    mix = {**MIX, "cycle": {"order": 3}}
    big = 2**31 + 12345

    def recs(seed, window=45.0, order=3):
        return serve.open_recs({**mix, "cycle": {"order": order}}, 2.0, seed,
                               15.0, window, 50304, 1024)

    def shape(r):
        return (r.due, len(r.prompt), r.out_len)

    a, b = recs(big), recs(7)
    assert [(r.due, r.prompt) for r in a] == [(r.due, r.prompt) for r in recs(big)]
    # the same lengths at the same times, other token ids
    assert [shape(r) for r in a] == [shape(r) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    dues = np.array([r.due for r in a])
    assert dues[0] >= 0.0 and np.all(np.diff(dues) > 0) and dues[-1] < 60.0
    inside = [r for r in a if r.due >= 15.0]
    pre = a[:len(a) - 90]
    assert len(inside) == 90 and 15 <= len(pre) <= 45
    # the window's requests: the work of ``requests`` and ``poisson_dues``
    want = traffic.requests(MIX, 1, 90, 50304, 1024)
    assert sorted(len(r.prompt) for r in inside) == sorted(
        len(r["prompt"]) for r in want)
    assert sorted(r.out_len for r in inside) == sorted(r["out_len"] for r in want)
    gaps = np.diff([r.due for r in a])
    first = 2 * (inside[0].due - 15.0)      # half of it lies in the window
    b_dues = traffic.poisson_dues(2.0, 1, 45.0)
    assert np.allclose(np.sort(np.append(gaps[-89:], first)),
                       np.sort(np.diff(b_dues, prepend=-b_dues[0])))
    # the ramp is the round run backwards: its last request is the round's
    # last, at the distance of the round's first gap
    assert [shape(r)[1:] for r in pre] == [shape(r)[1:] for r in inside[-len(pre):]]
    assert np.allclose(gaps[:len(pre) - 1], gaps[-(len(pre) - 1):])
    assert np.isclose(inside[0].due - pre[-1].due, first)
    # a shorter window (a traced run's) is a round of its own length, and the
    # ramp goes around it more than once
    short = recs(7, window=8.0)
    assert sum(15.0 <= r.due < 23.0 for r in short) == 16 and len(short) > 40
    # the order is the mix's
    other = recs(big, order=4)
    assert [shape(r) for r in other] != [shape(r) for r in a]
    assert sorted(len(r.prompt) for r in other[-90:]) == sorted(
        len(r.prompt) for r in inside)


class _State:
    def __init__(self):
        self.finished = False


class _Req:
    def __init__(self, n, on_token):
        self.left, self.on_token, self.state, self.uid = n, on_token, _State(), 0


class _Metrics:
    step_lat_s, step_batch, tokens_generated, preemptions = [], [], 0, 0
    prefill = {"prefill_only_steps": 0, "chunk_tokens": 0,
               "interleaved_steps": 0}


class SlowScheduler:
    """Every step takes 100 ms and gives each live request one token."""

    def __init__(self):
        self.live, self.metrics = [], _Metrics()

    queue_depth = 0

    @property
    def live_count(self):
        return len(self.live)

    def submit(self, prompt, max_new_tokens, on_token):
        self.live.append(_Req(max_new_tokens, on_token))
        return self.live[-1]

    def step(self):
        time.sleep(0.1)
        for r in self.live:
            r.on_token(r, 1)
            r.left -= 1
            r.state.finished = r.left == 0
        self.live = [r for r in self.live if r.left]

    def cancel(self, uid):
        pass


def test_ttft_counts_from_the_due_time_and_lateness_is_reported():
    # two requests due at the same instant: the second is submitted in the
    # same pass, both get their first token one 100 ms step later; the third
    # falls due in the middle of that step
    recs = [serve.Rec(0.05, [1, 2, 3], 2), serve.Rec(0.05, [1, 2, 3], 2),
            serve.Rec(0.1, [4], 1)]
    sent, counters, opened, end = serve.drive(
        SlowScheduler(), recs, ramp=0.0, seconds=0.6, drain=2.0,
        tracer=Tracer(False))
    assert len(sent) == 3 and all(r.req.state.finished for r in sent)
    for r in sent:
        late = r.submitted - r.due
        assert late >= 0
        assert r.times[0] - r.due >= 0.1           # the step, from the DUE time
        assert abs((r.times[0] - r.submitted) + late - (r.times[0] - r.due)) < 1e-9
    # the third was due while the scheduler was mid-step: it waited for it
    assert sent[2].submitted - sent[2].due > 0.02
    assert set(counters) >= {"dispatches", "decode_rows", "prefill_tokens"}
    assert opened is not None and end >= 0.25


def test_closed_loop_keeps_the_system_full():
    made = []

    def more(k):
        made.append(k)
        return [serve.Rec(None, [1], 1) for _ in range(4)]

    sched = SlowScheduler()
    sent, *_ = serve.drive(sched, more(0), ramp=0.0, seconds=0.45, drain=0.0,
                           tracer=Tracer(False), outstanding=4, more=more)
    assert len(sent) >= 12 and made[:3] == [0, 1, 2]
