"""Forward-only inference engine (ref/reward logprob recomputation).

Capability parity: realhf/impl/model/backend/inference.py
(`PipelinableInferenceEngine`) — holds frozen params on a mesh, serves
`forward` with the same packing/unpacking contract as TrainEngine, no
optimizer state.
"""

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import Engine
from areal_tpu.base.distributed import to_host
from areal_tpu.engines import packing
from areal_tpu.engines.offload import HostOffloadMixin
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.parallel import sharding


class InferenceEngine(HostOffloadMixin, Engine):
    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, Any],
        mesh: Mesh,
        compute_dtype=jnp.bfloat16,
    ):
        self.cfg = cfg
        self.mesh = mesh
        if jax.default_backend() == "cpu":
            compute_dtype = jnp.float32
        self.compute_dtype = compute_dtype
        (
            self._use_flash,
            self._cp_mesh,
            self._pp_mesh,
            self._pp_microbatches,
            self.batch_shard,
        ) = sharding.attn_dispatch(mesh, cfg)
        self._fwd_fns: Dict[Any, Callable] = {}
        self.set_params(params)

    def set_params(self, params) -> None:
        # Never alias the source engine's live, later-donated buffers.
        self._take_params(params, copy_aliases=True)

    def get_params(self):
        self._ensure_loaded()
        return self.params

    def train_batch(self, *a, **k):
        raise NotImplementedError("InferenceEngine cannot train")

    def forward(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        post_fn: Callable,
        output_key: str,
        token_key: str = "packed_input_ids",
        extra_keys: Sequence[str] = (),
    ) -> SequenceSample:
        self._ensure_loaded()
        fwd = self._get_fwd_fn(post_fn)
        outs = []
        for mb, blocks in packing.split_sharded(sample, mb_spec):
            pk = packing.pack_sample(
                mb,
                token_key,
                extra_keys=extra_keys,
                n_rows_multiple=self.batch_shard,
                max_tokens_per_row=mb_spec.max_tokens_per_mb,
                shard_blocks=blocks,
            )
            batch = {
                k: sharding.place_rows(
                    self.mesh, v, sharding.batch_pspec()
                )
                for k, v in pk.arrays.items()
            }
            dense = to_host(fwd(self.params, batch))
            outs.append(
                SequenceSample(
                    keys={output_key},
                    ids=list(mb.ids),
                    seqlens={
                        output_key: [list(s) for s in mb.seqlens[token_key]]
                    },
                    data={output_key: pk.unpack(dense)},
                )
            )
        result = SequenceSample.gather(outs)
        order = {i: n for n, i in enumerate(result.ids)}
        return result.select_idx([order[i] for i in sample.ids])

    def _get_fwd_fn(self, post_fn):
        if post_fn in self._fwd_fns:
            return self._fwd_fns[post_fn]
        cfg, mesh = self.cfg, self.mesh
        use_flash = self._use_flash
        cp_mesh = self._cp_mesh
        pp_mesh, pp_mbs = self._pp_mesh, self._pp_microbatches
        # A Gated DeltaNet layer's chunked rule: the backend's form on one
        # device, the `jnp` form on a mesh (`linear_attn_forward`).
        row_kernel = None if mesh.devices.size == 1 else mesh

        @jax.jit
        def fwd(params, batch):
            x, _ = tfm.hidden_states(
                params,
                cfg,
                batch["tokens"],
                batch["segment_ids"],
                positions=batch["positions"],
                use_flash=use_flash,
                cp_mesh=cp_mesh,
                pp_mesh=pp_mesh,
                pp_microbatches=pp_mbs,
                row_kernel=row_kernel,
            )
            return post_fn(
                tfm.per_token_output(
                    params,
                    cfg,
                    x,
                    batch["tokens"],
                    batch["segment_ids"],
                    mesh=mesh,
                ),
                batch,
            )

        self._fwd_fns[post_fn] = fwd
        return fwd
