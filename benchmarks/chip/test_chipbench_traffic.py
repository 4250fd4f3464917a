"""The traffic generator is deterministic in the seed, and every seed
asks for the same sizes in another order."""

import json
import numpy as np
import pytest

from chipbench import spec
from chipbench import traffic as TR

BIG = 2 ** 40 + 12345


def _mix(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", ["decode-s4", "longprompt-s4"])
def test_offline_batches_same_work_every_seed(name):
    t = _mix(name)
    a, b = TR.OfflineBatches(t, 49152, BIG), TR.OfflineBatches(t, 49152, 7)
    again = TR.OfflineBatches(t, 49152, BIG)
    for i in range(3):
        ra, rb, rr = a.batch(i), b.batch(i), again.batch(i)
        assert [(r.prompt, r.max_new_tokens) for r in ra] == \
            [(r.prompt, r.max_new_tokens) for r in rr]
        assert [(len(r.prompt), r.max_new_tokens) for r in ra] == \
            [(len(r.prompt), r.max_new_tokens) for r in rb]
        assert [r.prompt for r in ra] != [r.prompt for r in rb]
        assert all(len(r.prompt) + r.max_new_tokens <= t["max_seq"]
                   for r in ra)
        lo, hi = t["prompt_len"]
        assert all(lo <= len(r.prompt) <= hi for r in ra)
    assert len({r.uid for i in range(3) for r in a.batch(i)}) == \
        3 * t["requests_per_batch"]


def test_input_pool_deterministic_in_seed():
    t = dict(_mix("stream-b1024"), batch=4, pool_batches=3)
    a, b = TR.InputPool(t, BIG), TR.InputPool(t, BIG)
    c = TR.InputPool(t, BIG + 1)
    np.testing.assert_array_equal(a.batches, b.batches)
    assert not np.array_equal(a.batches, c.batches)
    assert sorted(a.index(i) for i in range(3)) == [0, 1, 2]


def test_seeds_beyond_32_bits_differ():
    assert TR.seed_words(2 ** 40 + 3).generate_state(2).tolist() != \
        TR.seed_words(3).generate_state(2).tolist()


def test_reservoir_keeps_a_uniform_sample():
    kept, offer = TR.reservoir(np.random.default_rng(0), 4)
    for i in range(100):
        offer(i, i)
    assert len(kept) == 4 and len(set(kept)) == 4
    assert TR.sample_ids(list(range(10)), 3, np.random.default_rng(1),
                         must=[9])[0] == 9
