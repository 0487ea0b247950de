"""The benchmark's own byte-level tokenizer (a copy of the program's
`CharTokenizer` as `tests/fixtures.make_tokenizer()` builds it), so that a
prompt of n ASCII characters is exactly n tokens whatever a later PR does
to the fixture.  The system takes its tokenizer as an argument.

One difference: the EOS id is given by the caller, and `benchmark/run.py`
gives the first id PAST the model's vocabulary.  With random weights EOS
is a lottery (about one token in `vocab`); a row that ends early changes
the step's token total, the program jits `gae_packed` on that exact total,
and each new total costs a compilation of about 1.9 s inside the window
(3 of 10 runs of one cell, PR 22).  No sampled token equals an id outside
the vocabulary, so every response runs to its budget and every step does
the same work.  The program expects such sentinels (`models/transformer.py`
`_embed` clips ids past the table)."""


class ByteTokenizer:
    def __init__(self, eos_token_id, vocab_size=512):
        self.pad_token_id = 256
        self.eos_token_id = int(eos_token_id)
        self.bos_token_id = 258
        self.vocab_size = max(vocab_size, 259)
        self.eos_token = "<eos>"
        self.pad_token = "<pad>"

    def encode(self, text, add_eos=False):
        ids = list(text.encode("utf-8"))
        if add_eos:
            ids.append(self.eos_token_id)
        return ids

    def decode(self, ids, skip_special_tokens=True):
        return bytes(i for i in ids if 0 <= int(i) < 256).decode(
            "utf-8", errors="replace"
        )

    def __call__(self, texts, truncation=False, max_length=None, **kw):
        if isinstance(texts, str):
            texts = [texts]
        out = []
        for t in texts:
            ids = self.encode(t)
            if truncation and max_length is not None:
                ids = ids[:max_length]
            out.append(ids)
        return {"input_ids": out, "length": [len(x) for x in out]}
