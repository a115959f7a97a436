"""Traffic generation: the same seed gives the same schedule, every
seed the same work, lengths stay inside the mix's range, and the
driver's lateness accounting."""
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.distributions import quantiles
from benchmarks.generators import batch_stream, closed_loop, open_loop

CHAT = harness.load_mix("chat-steady")
BACKLOG = harness.load_mix("decode-backlog")
BIG = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


def lens(gen, phase="window"):
    return sorted((r["prompt"].size, r["max_new_tokens"])
                  for r in gen.requests if r["phase"] == phase)


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_open_loop_same_seed_same_schedule(seed):
    a = open_loop.Generator(CHAT, seed, 40, 32768)
    b = open_loop.Generator(CHAT, seed, 40, 32768)
    assert [r["due"] for r in a.requests] == [r["due"] for r in b.requests]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a.requests, b.requests))


def test_open_loop_every_seed_offers_the_same_work():
    a = open_loop.Generator(CHAT, 1, 40, 32768)
    b = open_loop.Generator(CHAT, BIG, 40, 32768)
    assert a.offered() == b.offered()
    assert lens(a) == lens(b)
    warm = lambda g: [r["due"] for r in g.requests if r["phase"] == "warm"]
    assert warm(a) != warm(b)
    assert not np.array_equal(a.requests[0]["prompt"][:8],
                              b.requests[0]["prompt"][:8])


def test_open_loop_ranges_and_window():
    g = open_loop.Generator(CHAT, 3, 40, 32768)
    rate = CHAT["arrivals"]["rate_per_s"]
    win = [r for r in g.requests if r["phase"] == "window"]
    assert len(win) == round(rate * 40)
    assert all(g.warm_s <= r["due"] < g.warm_s + 40 for r in win)
    assert all(r["due"] < g.warm_s for r in g.requests
               if r["phase"] == "warm")
    assert all(32 <= r["prompt"].size <= 2048 for r in g.requests)
    assert all(8 <= r["max_new_tokens"] <= 384 for r in g.requests)
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 32768
               for r in g.requests)
    dues = [r["due"] for r in g.requests]
    assert dues == sorted(dues)
    # due() hands each request out once, when it is due
    first = g.due(g.warm_s / 2)
    assert all(r["due"] <= g.warm_s / 2 for r in first)
    assert g.next_due() > g.warm_s / 2
    rest = g.due(1e9)
    assert len(first) + len(rest) == len(g.requests) and g.due(1e9) == []


def test_quantile_grids():
    q = quantiles(CHAT["prompt_len"], 1001)
    assert q[500] == 384 and q.min() == 32 and q.max() == 2048
    u = quantiles({"dist": "uniform", "min": 64, "max": 128}, 64)
    assert u.min() >= 64 and u.max() <= 128 and len(set(u)) > 30
    e = quantiles({"dist": "exponential", "mean": 2.0}, 10000)
    assert e.mean() == pytest.approx(2.0, rel=0.01)
    with pytest.raises(ValueError):
        quantiles({"dist": "zipf"}, 3)


def test_mix_base_overrides(tmp_path, monkeypatch):
    import json
    import os
    d = tmp_path / "benchmarks" / "traffic"
    d.mkdir(parents=True)
    (d / "a.json").write_text(json.dumps({"x": 1, "y": {"z": 2}}))
    (d / "b.json").write_text(json.dumps({"base": "a", "x": 5}))
    monkeypatch.setattr(harness, "HERE", str(tmp_path / "benchmarks"))
    assert harness.load_mix("b") == {"x": 5, "y": {"z": 2}}
    assert os.path.isdir(harness.HERE)


def test_closed_loop_keeps_every_client_busy():
    g = closed_loop.Generator(BACKLOG, BIG, 40, 32768)
    first = g.due(0.0)
    assert len(first) == BACKLOG["clients"] and g.due(0.1) == []
    g.finished(first[0])
    g.finished(first[1])
    nxt = g.due(g.warm_s + 1.0)
    assert len(nxt) == 2 and all(r["phase"] == "window" for r in nxt)
    assert all(r["phase"] == "warm" for r in first)
    assert all(64 <= r["prompt"].size <= 128
               and 256 <= r["max_new_tokens"] <= 512 for r in g.requests)
    other = closed_loop.Generator(BACKLOG, 4, 40, 32768)
    assert sorted(r["max_new_tokens"] for r in other.requests) == \
        sorted(r["max_new_tokens"] for r in g.requests)


def test_batch_stream_rows_differ_and_repeat_by_seed():
    mix = {"batch": 2, "seq": 64}
    a = batch_stream.Generator(mix, BIG, 1, 512).batches()
    b = batch_stream.Generator(mix, BIG, 1, 512).batches()
    t0, l0 = next(a)
    t1, _ = next(a)
    assert np.array_equal(t0, next(b)[0])
    assert t0.shape == (2, 64) and t0.dtype == np.int32
    assert np.array_equal(t0[:, 1:], l0[:, :-1])      # shifted by one
    assert not np.array_equal(t0[0], t0[1]) and not np.array_equal(t0, t1)


def test_lateness_and_window_accounting(capsys):
    """A request is timed from the instant it was due, not from when a
    late generator got round to submitting it."""
    from benchmarks.drivers import serving_engine as drv

    def rec(due, submit, admit, first, last, n, in_window=True):
        r = drv.Record({"prompt": np.zeros(10, np.int32),
                        "max_new_tokens": n},
                       types.SimpleNamespace(admit_t=admit), due, submit,
                       in_window)
        r.first_t, r.last_t, r.seen = first, last, n
        return r

    done = [rec(10.0, 10.5, 10.6, 11.0, 12.0, 11),
            rec(11.0, 11.0, 11.0, 11.2, 11.7, 6),
            rec(5.0, 5.0, 5.0, 5.5, 10.5, 9, in_window=False)]
    counts = {"t_open": 10.0, "t_close": 20.0, "window_tokens": 17,
              "steps": 3}
    win, e2e, samples = drv.window_metrics(done, counts, 10.0, False)
    assert len(win) == 2
    assert samples["ttft_ms"] == pytest.approx([1000.0, 200.0])
    assert samples["tpot_ms"] == pytest.approx([100.0, 100.0])
    assert samples["queue_wait_ms"] == pytest.approx([600.0, 0.0])
    assert e2e["out_tok_s"] == pytest.approx(1.7)
    assert '"generator_late_ms": {"n": 2' in capsys.readouterr().out
