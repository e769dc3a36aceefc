"""The traffic generator: a seed fixes the traffic, every seed gets the
same sizes and gaps, and each mix's lengths follow its stated parameters."""
import numpy as np
import pytest

from bench import gen, harness

LM_MIXES = ["decode-backlog", "chat-prefix"]


def _items(mix_name, seed, seconds=30.0):
    mix = harness.load_json("traffic", mix_name)
    return mix, gen.lm_items(mix, seed, 151936, seconds)


@pytest.mark.parametrize("mix_name", LM_MIXES)
def test_same_seed_same_traffic(mix_name):
    _, a = _items(mix_name, 2 ** 33 + 5)
    _, b = _items(mix_name, 2 ** 33 + 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.out_len, x.due_s, x.prefix) == (y.out_len, y.due_s, y.prefix)


@pytest.mark.parametrize("mix_name", LM_MIXES)
def test_seeds_share_sizes_not_order(mix_name):
    mix, a = _items(mix_name, 1)
    _, b = _items(mix_name, 2)
    block = mix["block"]
    n = len(a) // block * block
    assert n and len(a) == len(b)
    for attr in ("out_len",):
        xa = [getattr(i, attr) for i in a[:n]]
        xb = [getattr(i, attr) for i in b[:n]]
        assert sorted(xa) == sorted(xb) and xa != xb
    assert sorted(len(i.prompt) for i in a[:n]) == \
        sorted(len(i.prompt) for i in b[:n])
    assert not np.array_equal(a[0].prompt, b[0].prompt)


@pytest.mark.parametrize("mix_name", LM_MIXES)
def test_lengths_follow_the_mix(mix_name):
    mix, items = _items(mix_name, 7)
    pre = mix.get("shared_prefix", {}).get("len", 0)
    plen = np.array([len(i.prompt) - (pre if i.prefix >= 0 else 0)
                     for i in items])
    out = np.array([i.out_len for i in items])
    for x, spec in ((plen, mix["prompt_len"]), (out, mix["output_len"])):
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 0.02 * spec["median"] + 1
        inner = x[(x > spec["min"]) & (x < spec["max"])]
        sigma = np.std(np.log(inner))
        assert sigma <= spec["sigma"] * 1.02
    if "shared_prefix" in mix:
        sp = mix["shared_prefix"]
        assert all(i.prefix >= 0 for i in items)
        full = items[:len(items) // mix["block"] * mix["block"]]
        counts = np.bincount([i.prefix for i in full], minlength=sp["count"])
        w = np.arange(1, sp["count"] + 1) ** -sp["zipf"]
        # whole blocks hold the Zipf shares rounded to 1/block
        assert np.allclose(counts / counts.sum(), w / w.sum(),
                           atol=1.0 / mix["block"])
        first = {i.prefix: i.prompt[:pre] for i in items}
        for i in items:
            assert np.array_equal(i.prompt[:pre], first[i.prefix])


def test_open_loop_rates_and_bursts():
    """Burst and base segments hold Poisson counts at their rates: over
    many periods the counts average to rate x length, with the spread of a
    Poisson count, not a fixed number per segment."""
    mix = harness.load_json("traffic", "chat-prefix")
    b, r = mix["burst"], mix["rate"]
    periods = 400
    t = gen.arrival_times(mix, periods * b["every_s"])
    assert np.all(np.diff(t) >= 0)
    assert t.min() >= -mix["preroll_s"]
    assert t.max() < periods * b["every_s"] + mix["tail_s"]
    k = np.floor(t / b["every_s"])
    in_burst = (t - k * b["every_s"]) < b["length_s"]
    ok = (k >= 0) & (k < periods)
    n_burst = np.bincount(k[ok & in_burst].astype(int), minlength=periods)
    n_rest = np.bincount(k[ok & ~in_burst].astype(int), minlength=periods)
    for n, lam in ((n_burst, r * b["factor"] * b["length_s"]),
                   (n_rest, r * (b["every_s"] - b["length_s"]))):
        se = np.sqrt(lam / periods)
        assert abs(n.mean() - lam) < 4 * se
        assert 0.7 * lam < n.var() < 1.3 * lam      # Poisson: var = mean


def test_arrivals_are_one_draw_for_every_seed():
    mix = harness.load_json("traffic", "chat-prefix")
    _, a = _items("chat-prefix", 11)
    _, b = _items("chat-prefix", 2 ** 33 + 12)
    assert [i.due_s for i in a] == [i.due_s for i in b]
    assert np.array_equal(gen.arrival_times(mix, 30.0),
                          gen.arrival_times(mix, 30.0))
    other = gen.arrival_times(dict(mix, arrival_seed=1), 30.0)
    due = [i.due_s for i in a]
    assert len(other) != len(due) or not np.allclose(other, due)
