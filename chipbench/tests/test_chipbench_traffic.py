"""The traffic generator: reproducible from its seed, the same work for
every seed, and lengths the cell has warmed."""

import json
import pathlib

import numpy as np
import pytest

import benchtree  # noqa: F401  (puts the repository on sys.path)
from chipbench.bench import traffic

MIXES = pathlib.Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11])
def test_open_schedule_is_reproducible(seed):
    mix = _mix("chat")
    a = traffic.open_schedule(mix, seed, 40.0, 6.0)
    b = traffic.open_schedule(mix, seed, 40.0, 6.0)
    assert a == b and len(a) == 240
    assert a[0].due == 0.0 and a[-1].due < 40.0
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))


def test_seeds_reorder_the_same_work():
    mix = _mix("chat")
    a = traffic.open_schedule(mix, 1, 40.0, 6.0)
    b = traffic.open_schedule(mix, 2, 40.0, 6.0)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    gaps = [np.diff([r.due for r in s]) for s in (a, b)]
    assert gaps[0].sum() < 40.0
    assert not np.allclose(gaps[0], gaps[1])
    # the same sequence, each request with its size and the gap after it,
    # started at another place: the bursts are the same in every seed
    def cycle(s):
        gap = np.diff([r.due for r in s] + [40.0 + s[0].due])
        return [(r.prompt_len, r.max_new) for r in s], gap
    (sa, ga), (sb, gb) = cycle(a), cycle(b)
    k = next(k for k in range(240) if sa[k:] + sa[:k] == sb
             and np.allclose(np.roll(ga, -k)[:-1], gb[:-1]))
    assert 0 < k < 240
    # it starts after one of the mix's four longest gaps, so the gap that
    # closes the window is one of them: no burst is cut in two
    assert mix["arrivals"]["starts"] == 4
    assert ga[-1] >= np.sort(ga)[-4] and gb[-1] >= np.sort(gb)[-4]


def test_lengths_stay_on_the_warmed_shapes():
    mix = _mix("chat")
    plan = traffic.open_schedule(mix, 3, 40.0, 6.0)
    assert {r.prompt_len for r in plan} <= set(traffic.prompt_lengths(mix))
    assert all(16 <= r.max_new <= 512 for r in plan)
    assert all(p + o <= 2048 for p, o in
               ((r.prompt_len, r.max_new) for r in plan))


@pytest.mark.parametrize("name", ["docs", "batch"])
def test_closed_stream_is_reproducible(name):
    mix = _mix(name)

    def take(seed, n=300):
        it = traffic.closed_stream(mix, seed, mix.get("pool", 0))
        return [next(it) for _ in range(n)]

    assert take(7) == take(7)
    if name == "batch":
        assert take(7) != take(8)
    # one sequence of ``block`` sizes, repeated, from a place the seed picks
    # among the first ``starts``
    block = mix["block"]
    sizes = [(r.prompt_len, r.max_new) for r in take(7, 2 * block)]
    assert sizes[:block] == sizes[block:]
    other = [(r.prompt_len, r.max_new) for r in take(8, block)]
    assert any(sizes[k: k + block] == other for k in range(block))
    if name == "docs":
        # every seed the same sequence from the same place (its prompts'
        # tokens still follow the seed)
        assert mix["starts"] == 1
        assert all([(r.prompt_len, r.max_new) for r in take(s, block)]
                   == sizes[:block] for s in (8, 9, 2 ** 31 + 5))
    if name == "docs":
        assert {r.prompt_len for r in take(7)} <= {1024, 1536, 2048, 3072}
        assert all(16 <= r.max_new <= 64 for r in take(7))
    else:
        assert {r.item for r in take(7)} == set(range(mix["pool"]))


def test_prompt_tokens_follow_the_seed():
    a = traffic.prompt_tokens(5, 3, 64, 1000)
    assert a.dtype == np.int32 and a.shape == (64,) and a.max() < 1000
    assert np.array_equal(a, traffic.prompt_tokens(5, 3, 64, 1000))
    assert not np.array_equal(a, traffic.prompt_tokens(6, 3, 64, 1000))
