"""The traffic generator: reproducible from a seed, inside its bounds, and
the same set of sizes for every seed."""

import json
import sys
from collections import Counter
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import traffic  # noqa: E402

MIXES = {p.stem: json.loads(p.read_text())
         for p in (BENCH / "traffic").glob("*.json")}
BIG_SEED = 2**31 + 987654321


def _sizes(items):
    return (Counter(len(it.prompt) for it in items),
            Counter(it.max_new for it in items))


def test_every_mix_is_known():
    assert MIXES
    for name, mix in MIXES.items():
        assert mix["loop"] in ("open", "closed"), name


def test_open_loop_reproduces_and_keeps_bounds():
    for name, mix in MIXES.items():
        if mix["loop"] != "open":
            continue
        a = traffic.open_loop(mix, BIG_SEED, 12.0, 151936)
        b = traffic.open_loop(mix, BIG_SEED, 12.0, 151936)
        assert len(a) == round(mix["rate_rps"] * 12.0)
        for x, y in zip(a, b):
            assert x.due == y.due and x.max_new == y.max_new
            assert (x.prompt == y.prompt).all()
        p, o = mix["prompt"], mix["output"]
        assert all(p["min"] <= len(x.prompt) <= p["max"] for x in a)
        assert all(o["min"] <= x.max_new <= o["max"] for x in a)
        assert all(0 <= x.prompt.min() and x.prompt.max() < 151936
                   for x in a)
        dues = [x.due for x in a]
        assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 12.0


def test_open_loop_same_work_for_every_seed():
    """Every seed gets the same schedule; the seed draws the token ids."""
    mix = next(m for m in MIXES.values() if m["loop"] == "open")
    for block in (0, 1):
        a = traffic.open_loop(mix, 1, 20.0, 1000, block=block)
        b = traffic.open_loop(mix, BIG_SEED, 20.0, 1000, block=block)
        assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
            [(y.due, len(y.prompt), y.max_new) for y in b]
        assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    ri = traffic.run_in(mix, 1, 1000)
    assert [x.due for x in ri] == [x.due for x in traffic.run_in(
        mix, BIG_SEED, 1000)]
    assert len(ri) == round(mix["rate_rps"] * mix["run_in_s"])
    assert ri[0].due == -mix["run_in_s"] and ri[-1].due < 0
    # the later blocks start where the window ends
    assert traffic.open_loop(mix, 1, 20.0, 1000, block=1)[0].due == 20.0


def test_closed_stream_blocks_cover_the_distribution():
    for name, mix in MIXES.items():
        if mix["loop"] != "closed":
            continue
        k = mix["block"]
        it = traffic.closed_stream(mix, BIG_SEED, 151936)
        first = [next(it) for _ in range(3 * k)]
        again = traffic.closed_stream(mix, BIG_SEED, 151936)
        assert all((x.prompt == next(again).prompt).all() for x in first)
        other = traffic.closed_stream(mix, 5, 151936)
        assert _sizes(first[:k]) == _sizes([next(other) for _ in range(k)])
        p = mix["prompt"]
        assert all(p["min"] <= len(x.prompt) <= p["max"] for x in first)


def test_quantiles_follow_the_file():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
         "max": 2048}
    assert traffic.quantile(d, 0.5) == 512
    assert traffic.quantile(d, 1e-9) == 64 and traffic.quantile(d, 1 - 1e-9) == 2048
    u = {"dist": "uniform", "min": 1, "max": 16}
    assert [traffic.quantile(u, (i + 0.5) / 16) for i in range(16)] == \
        list(range(1, 17))
