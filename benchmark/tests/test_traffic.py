import numpy as np

from benchmark.harness.traffic import Generator

MIX = dict(arrival=dict(kind="closed", clients=4), prompt_tokens=[8, 48],
           output_tokens=[4, 12], block=16)


def _sizes(gen, n):
    plans = [gen.draw() for _ in range(n)]
    return [(len(p.prompt), p.max_new_tokens) for p in plans], plans


def test_every_seed_sends_the_same_sizes_in_the_same_order():
    a, pa = _sizes(Generator(MIX, 100, seed=1), 40)
    b, pb = _sizes(Generator(MIX, 100, seed=2 ** 31 + 7), 40)
    assert a == b
    assert not np.array_equal(pa[0].prompt, pb[0].prompt)   # other tokens
    assert sorted(a[:16]) == sorted(a[16:32]) and a[:16] != a[16:32]
    lens = [n for n, _ in a[:16]]
    assert min(lens) == 8 and max(lens) == 48


def test_same_seed_same_tokens():
    _, a = _sizes(Generator(MIX, 100, seed=5), 5)
    _, b = _sizes(Generator(MIX, 100, seed=5), 5)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(0 <= x.prompt.min() and x.prompt.max() < 100 for x in a)


def test_shared_prefix_and_buckets():
    gen = Generator(dict(MIX, shared_prefix_tokens=6), 100, seed=3)
    _, plans = _sizes(gen, 8)
    assert len({tuple(p.prompt[:6]) for p in plans}) == 1
    assert len({tuple(p.prompt[6:10]) for p in plans}) > 1
    assert gen.padded_prompt_lengths(16) == [16, 32, 48]


def test_poisson_gaps_have_the_asked_rate_in_every_block():
    mix = dict(MIX, arrival=dict(kind="poisson", rate_per_s=10.0), block=64)
    one = [Generator(mix, 100, seed=s) for s in (1, 2)]
    gaps = [[g.draw().gap_s for _ in range(64)] for g in one]
    assert gaps[0] == gaps[1]
    assert 0.06 < np.mean(gaps[0]) < 0.16
