"""The traffic generator: deterministic per seed, the same work for every
seed, the stated distributions, and mixes found from files alone."""
import json
import math
import shutil

import numpy as np
import pytest

from bench.lib import spec, traffic

MIXES = {n: spec.load_json(spec.BENCH / "traffic" / f"{n}.json")
         for n in ("chat-sysprompt", "decode-unshared")}


def _gen(mix, seed, rate=8.0, horizon=60.0):
    return traffic.generate(MIXES[mix], seed=seed, rate_rps=rate,
                            horizon_s=horizon, vocab_size=1000)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_schedule(mix):
    a, b = _gen(mix, 2**31 + 99), _gen(mix, 2**31 + 99)
    assert [(r.offset_s, r.gen_len, r.sampling, r.tenant) for r in a] == \
        [(r.offset_s, r.gen_len, r.sampling, r.tenant) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_seeds_offer_the_same_work_in_another_order(mix):
    a, b = _gen(mix, 1), _gen(mix, 2)
    for key in (lambda r: len(r.prompt), lambda r: r.gen_len,
                lambda r: r.tenant, lambda r: r.sampling is None):
        assert sorted(map(key, a)) == sorted(map(key, b))
    if "order_seed" in MIXES[mix]:
        # a fixed order: the same schedule, other token ids
        assert [(r.offset_s, r.gen_len, r.tenant) for r in a] == \
            [(r.offset_s, r.gen_len, r.tenant) for r in b]
        assert not np.array_equal(a[0].prompt, b[0].prompt)
    else:
        assert [r.gen_len for r in a] != [r.gen_len for r in b]
    gaps = lambda rs: sorted(np.diff([0.0] + [r.offset_s for r in rs]))
    np.testing.assert_allclose(gaps(a), gaps(b))


@pytest.mark.parametrize("mix,part", [("chat-sysprompt", "output"),
                                      ("decode-unshared", "output"),
                                      ("decode-unshared", "suffix")])
def test_lengths_follow_the_stated_lognormal(mix, part):
    m = MIXES[mix]
    d = m["output"] if part == "output" else m["prompt"]["suffix"]
    rs = _gen(mix, 5, rate=40.0)
    vals = np.array([r.gen_len if part == "output" else len(r.prompt)
                     for r in rs])
    assert vals.min() >= d["min"] and vals.max() <= d["max"]
    assert abs(np.median(vals) / d["median"] - 1) < 0.02
    inner = vals[(vals > d["min"]) & (vals < d["max"])]
    q75 = np.quantile(vals, 0.75)
    want = d["median"] * math.exp(0.6745 * d["sigma"])
    assert len(inner) > 0.5 * len(vals) and abs(q75 / want - 1) < 0.03


def test_chat_tenants_are_zipf_and_share_their_system_prompt():
    m = MIXES["chat-sysprompt"]
    rs = _gen("chat-sysprompt", 7, rate=40.0)
    n_t = m["prompt"]["tenants"]
    counts = np.bincount([r.tenant for r in rs], minlength=n_t)
    np.testing.assert_array_equal(
        counts, traffic.zipf_counts(n_t, m["prompt"]["tenant_zipf_s"],
                                    len(rs)))
    h = sum(1 / k for k in range(1, n_t + 1))
    assert abs(counts[0] / len(rs) - 1 / h) < 0.01
    sys_len = m["prompt"]["system_tokens"]
    for t in range(n_t):
        heads = {r.prompt[:sys_len].tobytes() for r in rs if r.tenant == t}
        assert len(heads) == 1
    suffix = np.array([len(r.prompt) - sys_len for r in rs])
    assert abs(np.median(suffix) / m["prompt"]["suffix"]["median"] - 1) \
        < 0.02


def test_sampling_share_and_seeds():
    m = MIXES["chat-sysprompt"]
    rs = _gen("chat-sysprompt", 9)
    greedy = sum(r.sampling is None for r in rs)
    assert greedy == round(len(rs) * m["sampling"]["greedy_share"])
    seeds = {r.sampling.seed for r in rs if r.sampling is not None}
    assert len(seeds) == len(rs) - greedy
    assert all(0 <= s < 2**31 for s in seeds)
    assert all(r.sampling is None for r in _gen("decode-unshared", 9))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_arrivals_are_poisson_at_the_rate_after_the_backlog(mix):
    rate = 10.0
    backlog = MIXES[mix]["arrival"].get("backlog", 0)
    rs = _gen(mix, 11, rate=rate, horizon=200.0)
    assert len(rs) == backlog + math.ceil(rate * 200.0)
    assert all(r.offset_s == 0.0 for r in rs[:backlog])
    gaps = np.diff([0.0] + [r.offset_s for r in rs[backlog:]])
    assert abs(gaps.mean() * rate - 1) < 0.01
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05  # exponential: CV 1


def test_a_new_mix_and_cell_load_from_files_alone(tmp_path):
    """A new mix and cell are added as files and BENCHMARK.json
    entries; the harness finds them by name."""
    for d in ("configs", "traffic", "cells", "metrics"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    shutil.copy(spec.BENCH / "configs" / "qwen2-1.5b.json",
                tmp_path / "bench" / "configs" / "qwen2-1.5b.json")
    mix = dict(MIXES["decode-unshared"], lead_in_s=3)
    mix["output"] = dict(mix["output"], median=64, min=16, max=128)
    (tmp_path / "bench" / "traffic" / "short-answers.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "cells" / "qwen2-1.5b.short-answers.json"
     ).write_text(json.dumps({"rate_rps": 3.5}))
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    bench["workloads"].append({"name": "qwen2-1.5b.short-answers",
                               "config": "qwen2-1.5b",
                               "traffic": "short-answers", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.find_cell("qwen2-1.5b.short-answers", root=tmp_path)
    assert cell.mix["output"]["median"] == 64 and cell.settings["rate_rps"] == 3.5
    assert cell.config["hidden_size"] == 1536
    # a metric without a workloads list reaches every cell reporting the
    # end-to-end metric it moves
    assert [m["name"] for m in cell.end_to_end] == [
        "itl_p95_ms", "output_tokens_per_s", "setup_s"]
    rs = traffic.generate(cell.mix, seed=3, rate_rps=3.5, horizon_s=20,
                          vocab_size=cell.config["vocab_size"])
    assert max(r.gen_len for r in rs) <= 128
