"""The serving traffic generator: seeded, in seconds, and the same sizes
and arrival times for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import gen

FILE = json.loads((Path(__file__).resolve().parents[2] / "chipbench"
                   / "traffic" / "repo_prefix_burst.json").read_text())
TR = FILE


def sizes(reqs):
    return sorted((len(r.tokens), r.prefix_len, r.max_new) for r in reqs)


def test_open_loop_is_seeded_and_in_seconds():
    a = gen.schedule(TR, 49152, 2**33 + 5, 20.0)
    b = gen.schedule(TR, 49152, 2**33 + 5, 20.0)
    assert [(r.due_s, r.tenant, r.tokens.tolist()) for r in a] == \
        [(r.due_s, r.tenant, r.tokens.tolist()) for r in b]
    due = [r.due_s for r in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
    # about the file's rate, in requests per second
    assert 0.5 * TR["rate_per_s"] * 20 < len(a) < 2 * TR["rate_per_s"] * 20
    for r in a:
        bt = TR["block_tokens"]
        assert r.prefix_len % bt == 0 and len(r.tokens) % bt == 0
        lo, hi = TR["shared_blocks"]
        assert lo * bt <= r.prefix_len <= hi * bt
        assert 1 <= r.max_new <= TR["output_cap"]
        assert r.tokens.min() >= 1 and r.tokens.max() < 49152


@pytest.mark.parametrize("stream", [
    lambda seed: gen.schedule(TR, 49152, seed, 20.0),
    lambda seed: gen.warmup(TR, 49152, seed)], ids=["window", "warmup"])
def test_seeds_change_order_and_content_not_work(stream):
    a, b = stream(1), stream(2)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert sizes(a) == sizes(b)
    assert [r.tokens.tolist() for r in a] != [r.tokens.tolist() for r in b]


def test_shared_prefixes_repeat_within_a_tenant():
    reqs = gen.schedule(TR, 49152, 7, 30.0)
    by = {}
    for r in reqs:
        by.setdefault(r.tenant, set()).add(tuple(r.tokens[:r.prefix_len]))
    assert all(len(p) == 1 for p in by.values())
    assert max(sum(1 for r in reqs if r.tenant == t) for t in by) > 1


@pytest.mark.parametrize("mode", ["saturated"])
def test_saturated_mode(mode):
    tr = dict(TR, mode=mode, saturated_requests=50)
    reqs = gen.schedule(tr, 49152, 3, 10.0)
    assert len(reqs) == 50 and all(r.due_s == 0 for r in reqs)


def test_warmup_is_its_own_stream():
    w = gen.warmup(TR, 49152, 3)
    assert len(w) == TR["warmup_requests"]
    win = gen.schedule(TR, 49152, 3, 10.0)
    assert {r.rid for r in w}.isdisjoint({r.rid for r in win})
    assert np.mean([r.max_new for r in w]) > 1


def test_the_file_has_no_rate_until_the_knee_is_read():
    # the knee has been read: the rate and the limit are chip readings
    # (readings.py knee and control), and a mix without a rate is refused
    assert FILE["rate_per_s"] > 0 and FILE["served_logit_error_limit"] > 0
    unrated = {k: v for k, v in FILE.items() if k != "rate_per_s"}
    with pytest.raises(ValueError, match="rate_per_s"):
        gen.schedule(unrated, 49152, 3, 10.0)
