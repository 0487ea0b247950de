"""A micro-batch that FFD-packs into ONE row, trained and scored on an `f4`
mesh (four of the eight virtual CPU devices): `pack_sample` spreads its
sequences over the four rows the mesh needs, and the step is the same step
as on one device — the loss is a sum over valid tokens normalised by the
step's total weight and attention is confined to a segment, so where a
sequence sits changes only the order of fp32 sums.
"""

import jax
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import FinetuneSpec, OptimizerConfig
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines import packing
from areal_tpu.engines.train import TrainEngine
from areal_tpu.interfaces.ppo import _logprob_post
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.ops import functional as F

KEY = "packed_input_ids"
MB_SPEC = MicroBatchSpec(max_tokens_per_mb=512)
TRAIN = dict(
    loss_fn=F.sft_loss,
    loss_weight_fn=F.sft_label_count,
    extra_keys=("prompt_mask",),
)


def _engine(mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    cfg = tiny_config()
    return TrainEngine(
        cfg,
        tfm.init_params(cfg, jax.random.PRNGKey(0)),
        mesh,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        ftspec=FinetuneSpec(1, 8, 8),
    )


@pytest.fixture(scope="module")
def sample():
    """12 sequences of 16-39 tokens, 319 in all: one micro-batch under a
    cap of 512, which FFD packs into one row of 512."""
    rng = np.random.default_rng(31)
    lens = [int(n) for n in rng.integers(16, 40, size=12)]
    seqlens = [[n] for n in lens]
    return SequenceSample(
        keys={KEY, "prompt_mask"},
        ids=[f"q{i}" for i in range(len(lens))],
        seqlens={KEY: seqlens, "prompt_mask": [list(s) for s in seqlens]},
        data={
            KEY: rng.integers(
                1, tiny_config().vocab_size, size=sum(lens)
            ).astype(np.int32),
            "prompt_mask": np.concatenate(
                [np.arange(n) < max(2, n // 3) for n in lens]
            ),
        },
    )


@pytest.fixture(scope="module")
def steps(sample):
    """One `forward` and then one `train_batch` of the same sample from the
    same weights, on one device and on `f4`."""
    out = {}
    for mode in ("d1", "f4"):
        eng = _engine(mode)
        logp = eng.forward(
            sample.select_keys({KEY}), MB_SPEC,
            post_fn=_logprob_post, output_key="logprobs",
        )
        stats = eng.train_batch(sample, MB_SPEC, **TRAIN)
        out[mode] = {
            "batch_shard": eng.batch_shard,
            "logprobs": np.asarray(logp.data["logprobs"]),
            "stats": stats,
            "pack": dict(eng.last_pack_stats),
        }
    return out


def test_the_micro_batch_is_one_ffd_row(sample):
    assert len(sample.split(MB_SPEC)) == 1
    pk = packing.pack_sample(sample, KEY, max_tokens_per_row=512)
    assert (pk.n_rows, pk.row_len) == (1, 512)


def test_f4_fills_every_row_the_mesh_needs(steps):
    assert steps["d1"]["batch_shard"] == 1 and steps["f4"]["batch_shard"] == 4
    one, four = steps["d1"]["pack"], steps["f4"]["pack"]
    assert (one["n_rows"], one["empty_rows"], one["grid_tokens"]) == (1, 0, 512)
    assert (four["n_rows"], four["empty_rows"]) == (4, 0)
    assert four["real_tokens"] == one["real_tokens"] == 319
    # The parent laid this out as one row of 512 and three empty ones.
    parent = 319 / (4 * 512)
    assert four["grid_tokens"] == 4 * 128
    assert four["pack_efficiency"] == 319 / 512 > 4 * parent * 0.99
    assert four["pack_efficiency"] > parent


@pytest.mark.parametrize(
    "key", ["loss", "grad_norm", "update_norm", "n_micro_batches"]
)
def test_train_batch_on_f4_is_the_one_device_step(steps, key):
    one, four = steps["d1"]["stats"], steps["f4"]["stats"]
    assert set(one) == set(four)
    assert one["quarantined"] == four["quarantined"] == 0.0
    np.testing.assert_allclose(four[key], one[key], rtol=2e-4, atol=1e-6)


def test_every_train_stat_matches(steps):
    one, four = steps["d1"]["stats"], steps["f4"]["stats"]
    # The one stat that describes the layout, not the step: how many ways
    # the log-prob head split the vocabulary (model x fsdp).
    layout = "head/vocab_shards"
    assert (one[layout], four[layout]) == (1.0, 4.0)
    for k in one.keys() - {layout}:
        np.testing.assert_allclose(
            four[k], one[k], rtol=2e-4, atol=1e-6, err_msg=k
        )


def test_forward_logprobs_on_f4_are_the_one_device_logprobs(steps, sample):
    one, four = steps["d1"]["logprobs"], steps["f4"]["logprobs"]
    # One value per token, in the sample's packed order.
    assert one.shape == four.shape == (sum(sample.seqlens_of(KEY)),)
    assert np.abs(one).max() > 1.0
    np.testing.assert_allclose(four, one, rtol=2e-4, atol=2e-4)


def test_streamed_accumulation_reports_rows_too(sample):
    eng = _engine("f4")
    state = eng.train_stream_begin()
    eng.train_stream_chunk(state, sample, MB_SPEC, **TRAIN)
    eng.train_stream_chunk(state, sample, MB_SPEC, **TRAIN)
    eng.train_stream_end(state)
    pack = eng.last_pack_stats
    assert (pack["n_rows"], pack["empty_rows"]) == (8, 0)
    assert pack["grid_tokens"] == 8 * 128 and pack["real_tokens"] == 2 * 319


def test_1f1b_mem_row_chunks_still_get_whole_multiples(sample):
    """`_pack_row_chunks` slices rows in blocks of `batch_shard`: the
    balanced layout still hands it a multiple."""
    eng = _engine("f4")
    lens = sample.seqlens_of(KEY)
    pk = packing.pack_sample(
        sample, KEY, n_rows_multiple=eng.batch_shard,
        max_tokens_per_row=max(lens) * 2,  # FFD: 6 or 7 rows -> 8
    )
    assert pk.n_rows == 8
    eng.pipe_schedule, eng._pp_mesh = "1f1b-mem", object()
    chunks = eng._pack_row_chunks(pk.arrays)
    assert [c["tokens"].shape[0] for c in chunks] == [4, 4]
    assert all((c["segment_ids"] > 0).any(axis=1).all() for c in chunks)
