"""The one general prompt generator: seeded math rows in the verifier's own
format, each padded with filler words to an exact length in bytes (= tokens
under `benchmark/tokenizer.py`).

A traffic file (`benchmark/traffic/<name>.json`) gives:

    n_prompts, group     prompts per step and responses per prompt (GRPO)
    max_new_tokens       decode budget of every response
    dataset_max_length   the dataset's prompt cut-off (>= the longest prompt)
    prompt_len           {"dist": "uniform", "lo", "hi"} or
                         {"dist": "lognormal", "median", "sigma", "lo", "hi"},
                         either with an optional "jitter": j
    batches              distinct batches a run cycles through.  Default 1:
                         every step the same batch, its rows in order of
                         length; more: each batch in the order drawn (the
                         train engine's packed shapes follow the order)
    eos_reachable        false (default): the tokenizer's EOS id lies past
                         the model's vocabulary, so every response runs to
                         its budget.  true: the fixture's id 257, which a
                         random model samples about once in `vocab` tokens

Lengths are the distribution's quantiles at (i + 0.5) / n_prompts, clipped to
[lo, hi]: every batch of every seed offers the same multiset of lengths — a
fixed amount of work — while the seed decides which row gets which length,
the operands and the filler.  With a jitter each length moves by a seeded
whole number in [-j, j], so batches differ in their token totals as real
batches do.  The same seed gives the same rows, byte for byte; batch k is
rows [k * n_prompts, (k + 1) * n_prompts).
"""

import math
import random
from statistics import NormalDist

_WORDS = (
    "given that the sum of two integers is requested and no other fact is "
    "needed we note the tool returned a table of earlier results which may "
    "be ignored let x y z be numbers find the value then answer in a box"
).split()


def quantile_lengths(spec, n):
    lo, hi = int(spec["lo"]), int(spec["hi"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "uniform":
            x = lo + u * (hi - lo)
        elif spec["dist"] == "lognormal":
            x = spec["median"] * math.exp(
                spec["sigma"] * NormalDist().inv_cdf(u)
            )
        else:
            raise ValueError(f"unknown prompt_len dist {spec['dist']!r}")
        out.append(int(min(max(round(x), lo), hi)))
    return out


def generate(params, seed):
    """Rows for `MathCodePromptDataset`: query_id, prompt, task, solutions."""
    rng = random.Random(seed)
    spec = params["prompt_len"]
    jitter = int(spec.get("jitter", 0))
    lengths = []
    for _ in range(int(params.get("batches", 1))):
        batch = quantile_lengths(spec, params["n_prompts"])
        rng.shuffle(batch)
        lengths += [
            min(max(n + rng.randint(-jitter, jitter), spec["lo"]), spec["hi"])
            for n in batch
        ] if jitter else batch
    rows = []
    for i, length in enumerate(lengths):
        a, b = rng.randint(1, 50), rng.randint(1, 50)
        question = f"Compute {a} + {b}. "
        if length < len(question):
            raise ValueError(
                f"prompt length {length} is shorter than the question"
            )
        filler = ""
        while len(filler) < length - len(question):
            filler += rng.choice(_WORDS) + " "
        filler = filler[: length - len(question)]
        if filler:  # end on a space so the question starts a word
            filler = filler[:-1] + " "
        rows.append({
            "query_id": f"s{seed}-math-{i}",
            "prompt": filler + question,
            "task": "math",
            "solutions": [f"\\boxed{{{a + b}}}"],
        })
    return rows
