"""The traffic generator: same seed, same rows; every seed, the same work."""

import glob
import json
import os

import pytest

from benchmark import files
from benchmark.tokenizer import ByteTokenizer
from benchmark.traffic import math_prompts

TRAFFIC = sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(files.HERE, "traffic", "*.json"))
)


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_rows_and_exact_token_lengths(name):
    params = files.load_json("traffic", f"{name}.json")
    gen = files.load_module("traffic", params["generator"])
    rows = gen.generate(params, 7)
    assert rows == gen.generate(params, 7)
    n, batches = params["n_prompts"], params.get("batches", 1)
    assert len(rows) == n * batches
    assert len({r["query_id"] for r in rows}) == len(rows)
    tok = ByteTokenizer(eos_token_id=10**6)
    lens = [len(tok.encode(r["prompt"])) for r in rows]
    spec = params["prompt_len"]
    want = sorted(math_prompts.quantile_lengths(spec, n))
    for k in range(batches):  # every batch: the quantiles, give or take
        got = sorted(lens[k * n: (k + 1) * n])
        assert abs(sum(got) - sum(want)) <= spec.get("jitter", 0) * n
        if not spec.get("jitter"):
            assert got == want
    assert spec["lo"] <= min(lens) and max(lens) <= spec["hi"]
    assert max(lens) <= params["dataset_max_length"]
    for r in rows:  # the verifier's own row format
        assert r["task"] == "math" and r["solutions"][0].startswith("\\boxed{")
        assert r["prompt"].rstrip().endswith(".")


@pytest.mark.parametrize(
    "name",
    [t for t in TRAFFIC
     if "jitter" not in files.load_json("traffic", f"{t}.json")["prompt_len"]],
)
def test_every_seed_offers_the_same_multiset_of_lengths(name):
    params = files.load_json("traffic", f"{name}.json")
    a = math_prompts.generate(params, 1)
    b = math_prompts.generate(params, 2)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b
    )


def test_jittered_batches_differ_in_their_token_totals():
    params = {
        "n_prompts": 24, "batches": 6,
        "prompt_len": {"dist": "uniform", "lo": 40, "hi": 200, "jitter": 8},
    }
    rows = math_prompts.generate(params, 5)
    totals = [sum(len(r["prompt"]) for r in rows[k * 24: (k + 1) * 24])
              for k in range(6)]
    assert len(set(totals)) > 1
    assert all(40 <= len(r["prompt"]) <= 200 for r in rows)
    # Without a jitter every batch is the same multiset in another order.
    params["prompt_len"].pop("jitter")
    rows = math_prompts.generate(params, 5)
    a, b = ([len(r["prompt"]) for r in rows[k * 24: (k + 1) * 24]]
            for k in (0, 1))
    assert a != b and sorted(a) == sorted(b)


def test_lognormal_quantiles_have_the_stated_median():
    spec = {"dist": "lognormal", "median": 64, "sigma": 0.6, "lo": 1, "hi": 10**6}
    lens = math_prompts.quantile_lengths(spec, 101)
    assert lens[50] == 64 and lens == sorted(lens)
