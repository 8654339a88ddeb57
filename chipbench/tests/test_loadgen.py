"""The traffic generators, found by the name in the mix: reproducible from the seed, the same multiset of
sizes for every seed, the stated medians and clips."""
import statistics

from tiny import harness  # noqa: F401  (puts the repo on sys.path)

from chipbench import loadgen

CHAT = {"generator": "open_loop_poisson", "rate_per_s": 20.0,
        "prompt_tokens": {"median": 128, "sigma": 0.8, "lo": 16, "hi": 512},
        "output_tokens": {"median": 48, "sigma": 0.7, "lo": 4, "hi": 128}}


def test_open_loop_is_reproducible_and_seed_only_reorders():
    a = loadgen.generate(CHAT, 7, seconds=30, vocab=1000)
    b = loadgen.generate(CHAT, 7, seconds=30, vocab=1000)
    c = loadgen.generate(CHAT, 2**31 + 5, seconds=30, vocab=1000)
    assert a == b and a != c
    assert len(a) == len(c) == 600
    for key in ("max_new_tokens",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in c)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in c)


def test_open_loop_hits_medians_clips_and_rate():
    reqs = loadgen.generate(CHAT, 11, seconds=30, vocab=50272)
    prompts = [len(r["prompt"]) for r in reqs]
    outs = [r["max_new_tokens"] for r in reqs]
    assert abs(statistics.median(prompts) - 128) <= 2
    assert abs(statistics.median(outs) - 48) <= 1
    assert min(prompts) == 16 and max(prompts) == 512
    assert min(outs) >= 4 and max(outs) == 128
    dues = [r["due_s"] for r in reqs]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 30
    assert abs(dues[-1] - 30) < 0.2          # n requests over n / rate
    assert all(0 <= t < 50272 for r in reqs for t in r["prompt"])


def test_train_batches_differ_and_repeat():
    mix = {"generator": "train_batches", "batch": 4, "pool_batches": 3}
    pool, arrays = loadgen.generate(mix, 5, image=8, classes=10)
    pool2, arrays2 = loadgen.generate(mix, 5, image=8, classes=10)
    assert len(pool) == 3 and len(pool[0]) == 4
    assert (arrays[0][0] == arrays2[0][0]).all()
    assert not (arrays[0][0] == arrays[1][0]).all()
    assert pool[0][0][0].shape == (3, 8, 8) and pool[0][0][1].shape == (1,)


def test_a_mix_finds_its_generator_by_name_and_an_unknown_one_is_an_error():
    import pytest
    with pytest.raises(FileNotFoundError):
        loadgen.generate({"generator": "no_such_shape"}, 1)
