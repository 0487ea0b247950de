"""PPO / GRPO actor+critic algorithm interfaces.

Capability parity: realhf/impl/model/interface/ppo_interface.py
(`PPOActorInterface` :234-723, `PPOCriticInterface` :873) and
utils/ppo_functional.py (clipped losses, `get_packed_rewards`, KL control):

- generate: group sampling via the GeneratorEngine
- inference: recompute token logprobs (actor) / values (critic)
- train_step: KL rewards + terminal reward -> GAE (associative-scan kernel)
  or GRPO group-normalized advantages (`disable_value`), advantage
  normalization (global or per-group), minibatched clipped-PPO updates.

Alignment convention (established by the generator): every per-token key is
full-sequence-length aligned with packed_input_ids; index t carries the
quantity for predicting token t+1 (entries at t = L-1 are unused).
"""

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import (
    GenerationHyperparameters,
    Model,
    ModelInterface,
    register_interface,
)
from areal_tpu.base import integrity, logging, tracer
from areal_tpu.base.stats import merge_stats
from areal_tpu.ops import functional as F
from areal_tpu.ops.gae import gae_packed

logger = logging.getLogger("ppo")


# ---------------- jit loss fns (module-level: stable cache keys) ----------------


def _ppo_actor_loss_factory(
    eps_clip: float, behav_imp_weight_cap: Optional[float] = None
):
    """With `behav_imp_weight_cap` set, this is the DECOUPLED PPO objective
    (reference: ppo_functional.actor_loss_fn `proximal_logprobs` branch +
    arxiv 2505.24298 §4.2): the proximal policy (recomputed under the
    weights at train-step start) anchors the clipped ratio, while the
    behavior policy (the generator that sampled the tokens, possibly
    several versions old) enters as an importance weight
    exp(prox_logp - old_logp) on the per-token loss.  Tokens whose
    behavior weight exceeds the cap are masked out entirely — the
    variance-control rule AReaL uses instead of truncating the weight."""
    decoupled = behav_imp_weight_cap is not None

    def loss_fn(new_logp, batch):
        # `new_logp`: the engine's fused per-token next-token logprobs [B,S].
        mask = batch["loss_mask"] > 0
        old_logp = batch["old_logp"]
        adv = batch["advantages"]
        prox_logp = batch["prox_logp"] if decoupled else old_logp
        ratio = jnp.exp(jnp.where(mask, new_logp - prox_logp, 0.0))
        clipped = jnp.clip(ratio, 1.0 - eps_clip, 1.0 + eps_clip)
        pg = -jnp.minimum(ratio * adv, clipped * adv)
        stats = {}
        if decoupled:
            behav = jnp.exp(jnp.where(mask, prox_logp - old_logp, 0.0))
            capped = mask & (behav > behav_imp_weight_cap)
            pg = pg * jnp.where(capped, 0.0, behav)
            stats["behav_imp_weight_sum"] = jnp.where(mask, behav, 0.0).sum()
            stats["behav_cap_clip_sum"] = capped.sum().astype(jnp.float32)
        loss = jnp.where(mask, pg, 0.0).sum()
        n_clipped = (
            jnp.where(mask, (ratio * adv > clipped * adv), False)
        ).sum()
        approx_kl = jnp.where(mask, old_logp - new_logp, 0.0).sum()
        stats.update(
            actor_loss_sum=loss,
            importance_weight_sum=jnp.where(mask, ratio, 0.0).sum(),
            clip_ratio_sum=n_clipped.astype(jnp.float32),
            approx_kl_sum=approx_kl,
            # |adv| rides the device stats (not host numpy) so the value
            # is exact under sharded dispatch, where host arrays are
            # zero-filled for other members' rows but the placed batch is
            # globally real.
            advantage_abs_sum=jnp.where(mask, jnp.abs(adv), 0.0).sum(),
        )
        return loss, stats

    return loss_fn


def _ppo_critic_loss_factory(value_eps_clip: float):
    def loss_fn(values, batch):
        # `values` comes from the critic head: [B, S] fp32.
        mask = batch["loss_mask"] > 0
        old_v = batch["old_values"]
        ret = batch["returns"]
        v_clip = old_v + jnp.clip(
            values - old_v, -value_eps_clip, value_eps_clip
        )
        l1 = jnp.square(values - ret)
        l2 = jnp.square(v_clip - ret)
        loss = 0.5 * jnp.where(mask, jnp.maximum(l1, l2), 0.0).sum()
        return loss, {
            "value_loss_sum": loss,
            "value_clip_ratio_sum": jnp.where(mask, l2 > l1, False)
            .sum()
            .astype(jnp.float32),
        }

    return loss_fn


def _logprob_post(logp, batch):
    return logp  # engines already emit masked next-token logprobs [B, S]


def _value_post(values, batch):
    return jnp.where(batch["segment_ids"] > 0, values, 0.0)


def _mask_count(arrays) -> float:
    return float((arrays["loss_mask"] > 0).sum())


# ---------------- shared host-side plumbing ----------------


def _extract_layout(sample: SequenceSample):
    """Per-sequence (start, L, prompt_len, group_idx) from the packed batch."""
    lens = sample.seqlens_of("packed_input_ids")
    bounds = sample.cu_seqlens("packed_input_ids")
    pmask = np.asarray(sample.data["prompt_mask"])
    layout = []
    for i, L in enumerate(lens):
        s = bounds[i]
        pl = int(pmask[s : s + L].sum())
        layout.append((int(s), int(L), pl))
    # group index per sequence (batch element owning it)
    group_of = []
    for gi, group in enumerate(sample.seqlens["packed_input_ids"]):
        group_of += [gi] * len(group)
    return layout, group_of


def _seq_align_minus1(sample: SequenceSample, key: str) -> np.ndarray:
    """Re-align a (L-1)-per-seq key to full length L (trailing zero)."""
    src = np.asarray(sample.data[key])
    sb = sample.cu_seqlens(key)
    lens = sample.seqlens_of("packed_input_ids")
    out = np.zeros(sum(lens), np.float32)
    off = 0
    for i, L in enumerate(lens):
        seg = src[sb[i] : sb[i + 1]]
        out[off : off + len(seg)] = seg
        off += L
    return out


def _add_aligned_keys(sample: SequenceSample, arrays: Dict[str, np.ndarray]):
    seqlens = [list(s) for s in sample.seqlens["packed_input_ids"]]
    add = SequenceSample(
        keys=set(arrays.keys()),
        ids=list(sample.ids),
        seqlens={k: [list(s) for s in seqlens] for k in arrays},
        data=dict(arrays),
    )
    sample.update_(add)


def _select_group_seqs(sample: SequenceSample, keep) -> SequenceSample:
    """Rebuild a packed sample keeping only sequences `keep[gi]` (indices
    into each group) for every key carrying one entry per group sequence.
    Keys with a different per-group arity (e.g. a single prompt per group)
    pass through whole.  Host-side slicing — used once per train step by
    best-of-k selection."""
    k = max(len(g) for g in sample.seqlens["packed_input_ids"])
    new_seqlens: Dict[str, list] = {}
    new_data: Dict[str, np.ndarray] = {}
    for key in sample.keys:
        sl = sample.seqlens[key]
        bounds = sample.cu_seqlens(key)
        arr = np.asarray(sample.data[key])
        slices, new_sl = [], []
        si = 0
        for gi, group in enumerate(sl):
            idxs = keep[gi] if len(group) == k else range(len(group))
            new_sl.append([group[j] for j in idxs])
            for j in idxs:
                slices.append((int(bounds[si + j]), int(bounds[si + j + 1])))
            si += len(group)
        new_data[key] = (
            np.concatenate([arr[a:b] for a, b in slices])
            if slices
            else arr[:0]
        )
        new_seqlens[key] = new_sl
    return SequenceSample(
        keys=set(sample.keys),
        ids=list(sample.ids),
        seqlens=new_seqlens,
        data=new_data,
        # Same ids in the same order: per-id metadata — crucially the
        # shard_of tags that keep the batch on the sharded-dispatch
        # statistics path — carries over verbatim.
        metadata={k: list(v) for k, v in sample.metadata.items()},
    )


@dataclasses.dataclass
class PPOActorInterface(ModelInterface):
    """Reference defaults follow blog/AReaL_v0_2.md:85-103."""

    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters
    )
    n_minibatches: int = 4
    eps_clip: float = 0.2
    kl_ctl: float = 0.0
    # Adaptive KL control (reference: ppo_functional.py AdaptiveKLController,
    # enabled by ppo_interface.py adaptive_kl_ctl): `kl_ctl` becomes the
    # INITIAL coefficient and drifts to hold the measured policy↔ref KL at
    # `adaptive_kl_target` (interfaces/kl.py).  The live value rides recover
    # checkpoints via state_dict.
    kl_adaptive: bool = False
    adaptive_kl_target: float = 6.0
    adaptive_kl_horizon: float = 10000.0
    # Best-of-k (reference: ppo_interface.py generation_size vs group_size):
    # sample `generation_size` responses per prompt but train on only the
    # top `gconfig.n` by reward (ties broken toward longer responses).
    generation_size: Optional[int] = None
    discount: float = 1.0
    gae_lambda: float = 1.0
    max_reward_clip: float = 5.0
    reward_scaling: float = 1.0
    reward_bias: float = 0.0
    # Early stopping (reference: ppo_interface.py early_stop_imp_ratio /
    # early_stop_kl, checked inside the loss fn): when a minibatch's mean
    # importance ratio or approx-KL crosses the threshold, the REMAINING
    # minibatches of this step are skipped — the policy has drifted too
    # far off the behavior policy for more clipped updates to be sound.
    # (The reference aborts before applying the offending minibatch; the
    # fused jitted update here applies it, then stops.)
    early_stop_imp_ratio: Optional[float] = None  # e.g. 10.0
    early_stop_kl: Optional[float] = None  # e.g. 0.1
    disable_value: bool = False  # GRPO mode
    adv_norm: bool = True
    group_adv_norm: bool = False
    mask_no_eos_with_zero: bool = False
    # Per-token rewards (reference: ppo_interface.py use_dense_reward +
    # get_packed_reward_dense): read key "dense_rewards" (one score per
    # token, aligned with packed_input_ids) instead of a terminal scalar;
    # reward_delta uses consecutive-score differences (potential shaping).
    use_dense_reward: bool = False
    reward_delta: bool = True
    # Decoupled PPO for asynchronous RL (reference: ppo_functional.py
    # `proximal_logprobs` + behav_imp_weight_cap): when set, the proximal
    # policy is recomputed under the CURRENT weights at train-step start
    # and anchors the clipped ratio; the behavior (generator) logprobs
    # enter as an importance weight capped at this value (tokens above
    # the cap are masked out).  None = standard PPO — exactly today's
    # numerics, which is what `max_head_offpolicyness=0` configures.
    behav_imp_weight_cap: Optional[float] = None
    # Batch-level anomaly sentinels (numerical-integrity guard plane),
    # evaluated on host statistics BEFORE any gradient work is
    # dispatched — unlike early_stop_*, which reacts to per-minibatch
    # training stats, these reject the whole batch as unsound input:
    #   anomaly_kl_max: mean |logp - ref_logp| over response tokens
    #     above this -> KL blowup, quarantine the step;
    #   anomaly_imp_ratio_max R > 1: mean behavior importance weight
    #     exp(prox_logp - old_logp) outside [1/R, R] -> the behavior
    #     policy is too stale for clipped updates (decoupled PPO only);
    #   anomaly_degenerate_variance: every GRPO group's scores have
    #     zero variance -> all advantages are 0/eps noise (a poisoned or
    #     saturated reward).  Off by default: tiny eval trials with
    #     constant rewards are routine.
    # A tripped sentinel quarantines the step: the barrier path skips
    # all minibatches; the streamed path stops accumulating and forces
    # the engine to discard partial grads at train_stream_end.
    anomaly_kl_max: Optional[float] = None
    anomaly_imp_ratio_max: Optional[float] = None
    anomaly_degenerate_variance: bool = False

    def _batch_verdict(self, aux) -> int:
        """OR of interface-level verdict bits for this batch (0 = clean).

        Host-side means under sharded dispatch are computed over this
        member's own rows only — every member sees the same broadcast
        per-seq keys, and per-token anomalies large enough to matter
        dominate any single shard's mean, so the verdict stays
        SPMD-consistent in practice for the blowup thresholds it guards.
        """
        v = 0
        if (
            self.anomaly_kl_max is not None
            and aux.get("kl_abs_mean") is not None
            and aux["kl_abs_mean"] > self.anomaly_kl_max
        ):
            v |= integrity.KL_BLOWUP
        if (
            self.anomaly_imp_ratio_max is not None
            and aux.get("behav_imp_mean") is not None
        ):
            r = aux["behav_imp_mean"]
            cap = self.anomaly_imp_ratio_max
            if not (1.0 / cap <= r <= cap):
                v |= integrity.IMP_RATIO
        if self.anomaly_degenerate_variance and aux.get("degenerate_var"):
            v |= integrity.DEGENERATE_VAR
        return v

    def _kl(self):
        if getattr(self, "_kl_inst", None) is None:
            from areal_tpu.interfaces.kl import make_kl_controller

            object.__setattr__(
                self,
                "_kl_inst",
                make_kl_controller(
                    self.kl_ctl,
                    self.kl_adaptive,
                    self.adaptive_kl_target,
                    self.adaptive_kl_horizon,
                ),
            )
        return self._kl_inst

    def state_dict(self) -> Dict[str, float]:
        return self._kl().state_dict() if self.kl_adaptive else {}

    def load_state_dict(self, sd) -> None:
        if self.kl_adaptive and sd:
            self._kl().load_state_dict(sd)

    def generate(
        self, model: Model, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        g = self.gconfig
        if self.generation_size is not None:
            if self.generation_size < g.n:
                raise ValueError(
                    f"generation_size={self.generation_size} must be >= "
                    f"group size n={g.n}"
                )
            g = dataclasses.replace(g, n=self.generation_size)
        return model.engine.generate(
            sample, mb_spec, g, prompt_key="packed_prompts",
            seed=model.version,
        )

    def inference(
        self, model: Model, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        out = model.engine.forward(
            sample, mb_spec, post_fn=_logprob_post, output_key="logprobs",
            token_key="packed_input_ids",
        )
        return out

    def _filter_best_of_k(self, sample: SequenceSample) -> SequenceSample:
        """Keep the top `gconfig.n` of `generation_size` responses per
        prompt by reward, ties toward longer responses (reference topk,
        ppo_interface.py:43-48).  Runs before any advantage math so GRPO
        groups and GAE windows see only the kept sequences."""
        scores = np.asarray(sample.data["rewards"], np.float32)
        layout, _ = _extract_layout(sample)
        keep = []
        si = 0
        for group in sample.seqlens["packed_input_ids"]:
            k = len(group)
            resp_lens = [
                layout[si + j][1] - layout[si + j][2] for j in range(k)
            ]
            order = sorted(
                range(k),
                key=lambda j: (scores[si + j], resp_lens[j]),
                reverse=True,
            )[: self.gconfig.n]
            keep.append(sorted(order))
            si += k
        return _select_group_seqs(sample, keep)

    def _prepare_train_sample(
        self, model: Model, sample: SequenceSample, mb_spec: MicroBatchSpec
    ):
        """Everything before the minibatch loop: best-of-k filtering,
        KL-shaped rewards, GAE or GRPO advantages, advantage
        normalization, and the packed train sample with aligned keys.

        Shared by the barrier `train_step` (whole batch) and the
        streamed `train_stream_chunk` (one retired rollout chunk at a
        time); in the streamed case batch-global statistics (advantage
        moments for adv_norm, the ref-KL term) are computed over the
        chunk — the streaming per-micro-batch form of the estimator.
        GRPO group normalization is group-local either way, so it is
        exact under streaming as long as chunks respect group bounds.

        Returns (train_sample, extra_keys, aux)."""
        if (
            self.generation_size is not None
            and self.generation_size > self.gconfig.n
        ):
            sample = self._filter_best_of_k(sample)
        klv = self._kl().value
        # Sharded data plane: heavy per-token inputs hold real values only
        # for this member's own rows (layout metadata and per-seq keys are
        # global).  Per-row math below stays SPMD-consistent — loss_mask
        # and total weight derive from layout, GRPO group stats from
        # broadcast per-seq scores, per-token arrays are only consumed by
        # the rows' own devices.  Batch-GLOBAL statistics (advantage
        # moments for adv_norm, the policy↔ref KL for the stat and the
        # adaptive controller) cannot come from these host arrays; they
        # are computed by an exact in-mesh reduction over the placed
        # arrays instead (TrainEngine.masked_moments) — identical on
        # every member, so adaptive kl_ctl stays in lockstep.
        sharded = sample.shard_blocks() is not None
        layout, group_of = _extract_layout(sample)
        total = sum(L for (_, L, _) in layout)

        # --- behavior logprobs, ref logprobs, values: full-length aligned
        old_logp = _seq_align_minus1(sample, "packed_logprobs")
        # Decoupled PPO: one extra forward pass under the CURRENT weights
        # gives the proximal logprobs.  Runs before any update so all
        # minibatches share the same anchor (reference recomputes in the
        # inference MFC; here train_step owns it so the sync path pays
        # nothing when the cap is unset).
        prox_logp = None
        if self.behav_imp_weight_cap is not None:
            prox_out = model.engine.forward(
                sample.select_keys({"packed_input_ids"}),
                mb_spec,
                post_fn=_logprob_post,
                output_key="prox_logp",
                token_key="packed_input_ids",
            )
            prox_logp = np.asarray(
                prox_out.data["prox_logp"], np.float32
            )
        ref_logp = (
            _seq_align_minus1(sample, "packed_ref_logprobs")
            if "packed_ref_logprobs" in sample.keys
            else None
        )
        values = (
            np.asarray(sample.data["values"], np.float32)
            if "values" in sample.keys
            else np.zeros(total, np.float32)
        )
        scores = np.asarray(sample.data["rewards"], np.float32).copy()
        scores = np.clip(
            (scores + self.reward_bias) * self.reward_scaling,
            -self.max_reward_clip,
            self.max_reward_clip,
        )
        no_eos = np.asarray(sample.data["seq_no_eos_mask"], np.float32)
        if self.mask_no_eos_with_zero:
            scores = scores * (1.0 - no_eos)

        # --- per-token rewards on predict positions t in [pl-1, L-2]
        rewards = np.zeros(total, np.float32)
        loss_mask = np.zeros(total, np.float32)
        adv_full = np.zeros(total, np.float32)
        if ref_logp is not None and klv != 0.0:
            rewards -= klv * (old_logp - ref_logp)

        dense = None
        if self.use_dense_reward:
            if self.disable_value:
                raise ValueError(
                    "use_dense_reward requires the value (critic) mode — "
                    "GRPO group advantages are defined on scalar scores"
                )
            if "dense_rewards" not in sample.keys:
                raise ValueError(
                    "use_dense_reward needs a 'dense_rewards' key (one "
                    "score per token, aligned with packed_input_ids)"
                )
            dense = np.asarray(sample.data["dense_rewards"], np.float32)
            if len(dense) != total:
                raise ValueError(
                    f"dense_rewards must align with packed_input_ids: got "
                    f"{len(dense)} scores for {total} tokens"
                )
            # Same transform as scalar scores (bias/scale/clip); no-EOS
            # masking zeroes the whole truncated sequence's rewards.
            dense = np.clip(
                (dense + self.reward_bias) * self.reward_scaling,
                -self.max_reward_clip,
                self.max_reward_clip,
            )

        seq_slices = []
        for si, (s, L, pl) in enumerate(layout):
            lo, hi = s + max(pl - 1, 0), s + L - 1  # predict positions
            loss_mask[lo:hi] = 1.0
            if dense is not None:
                # Transition t (predicting token t+1) earns token t+1's
                # score — or the score DELTA (potential-based shaping) when
                # reward_delta (reference: get_packed_reward_dense).
                gain = dense[lo + 1 : hi + 1]
                if self.reward_delta:
                    gain = gain - dense[lo:hi]
                if self.mask_no_eos_with_zero:
                    gain = gain * (1.0 - no_eos[si])
                rewards[lo:hi] += gain
            else:
                rewards[hi - 1] += scores[si] if hi > lo else 0.0
            seq_slices.append((lo, hi))
        rewards *= loss_mask

        degenerate_var = None
        if self.disable_value:
            # GRPO: group-normalized terminal score broadcast over response.
            adv_seq = np.zeros(len(layout), np.float32)
            groups: Dict[int, list] = {}
            for si in range(len(layout)):
                groups.setdefault(group_of[si], []).append(si)
            degenerate_var = len(groups) > 0
            for gi, sis in groups.items():
                g_scores = scores[sis]
                mean = g_scores.mean()
                std = g_scores.std()
                if std > 0:
                    degenerate_var = False
                adv_seq[sis] = (g_scores - mean) / (std + 1e-5)
            for si, (lo, hi) in enumerate(seq_slices):
                adv_full[lo:hi] = adv_seq[si]
                # KL penalty still contributes per-token if configured.
            if ref_logp is not None and klv != 0.0:
                adv_full += -klv * (old_logp - ref_logp) * loss_mask
        else:
            # Pack response-only windows for GAE.
            r_parts, v_parts, seg_parts, boot_parts, lens_resp = (
                [], [], [], [], []
            )
            for si, (lo, hi) in enumerate(seq_slices):
                n = hi - lo
                if n == 0:
                    lens_resp.append(0)
                    continue
                r_parts.append(rewards[lo:hi])
                v_parts.append(values[lo:hi])
                seg_parts.append(np.full(n, si + 1, np.int32))
                b = np.zeros(n, np.float32)
                _, L, _ = layout[si]
                b[-1] = no_eos[si] * values[layout[si][0] + L - 1]
                boot_parts.append(b)
                lens_resp.append(n)
            if r_parts:
                r1 = np.concatenate(r_parts)
                # Upload, the `ppo/gae` program and the wait for it.
                with tracer.span("gae", cat="compute"):
                    adv1, ret1 = gae_packed(
                        jnp.asarray(r1),
                        jnp.asarray(np.concatenate(v_parts)),
                        jnp.asarray(np.concatenate(seg_parts)),
                        jnp.asarray(np.concatenate(boot_parts)),
                        self.discount,
                        self.gae_lambda,
                    )
                    adv1 = np.asarray(adv1)
                off = 0
                for si, (lo, hi) in enumerate(seq_slices):
                    n = hi - lo
                    adv_full[lo:hi] = adv1[off : off + n]
                    off += n

        # Batch-global moments: under sharded dispatch, reduce on device
        # (one cheap extra placement of [adv, klterm, mask]); otherwise
        # host numpy.  ref_kl uses the same pass — computed here, the
        # controller update stays at its reference timing (post-update
        # loop, ppo_interface.py:105).
        ref_kl = None
        batch_norm = self.adv_norm and not (
            self.group_adv_norm and not self.disable_value
        )
        if sharded and (batch_norm or ref_logp is not None):
            probe = sample.select_keys({"packed_input_ids"})
            arrays = {"loss_mask": loss_mask}
            vkeys = []
            if batch_norm:
                arrays["adv_probe"] = adv_full
                vkeys.append("adv_probe")
            if ref_logp is not None:
                arrays["klterm"] = (old_logp - ref_logp) * loss_mask
                vkeys.append("klterm")
            _add_aligned_keys(probe, arrays)
            mom = model.engine.masked_moments(
                probe, mb_spec, vkeys, mask_key="loss_mask"
            )
            cnt = mom["count"]
            if batch_norm and cnt > 0:
                s, ssq, _ = mom["adv_probe"]
                mean = s / cnt
                std = float(np.sqrt(max(ssq / cnt - mean * mean, 0.0)))
                m = loss_mask > 0
                adv_full[m] = (adv_full[m] - mean) / (std + 1e-5)
            if ref_logp is not None and cnt > 0:
                ref_kl = float(mom["klterm"][0] / cnt)
        if self.adv_norm:
            m = loss_mask > 0
            if not batch_norm:
                # group_adv_norm is row-local (a group is one batch
                # element, never split across shards): each member
                # normalizes with its own rows' real data; garbage
                # normalizations of other members' zero-filled rows are
                # never consumed by their devices.
                for gi in set(group_of):
                    gm = np.zeros_like(m)
                    for si, (lo, hi) in enumerate(seq_slices):
                        if group_of[si] == gi:
                            gm[lo:hi] = m[lo:hi]
                    if gm.any():
                        vals = adv_full[gm]
                        adv_full[gm] = (vals - vals.mean()) / (
                            vals.std() + 1e-5
                        )
            elif not sharded and m.any():
                vals = adv_full[m]
                adv_full[m] = (vals - vals.mean()) / (vals.std() + 1e-5)
            # (sharded batch_norm already applied from device moments)

        train_sample = sample.select_keys(
            {"packed_input_ids", "prompt_mask"}
        )
        aligned = {
            "old_logp": old_logp,
            "advantages": adv_full,
            "loss_mask": loss_mask,
        }
        extra_keys = ("old_logp", "advantages", "loss_mask")
        if prox_logp is not None:
            aligned["prox_logp"] = prox_logp
            extra_keys = extra_keys + ("prox_logp",)
        _add_aligned_keys(train_sample, aligned)
        # Sentinel inputs (host means over this member's rows).
        mt = float(loss_mask.sum())
        kl_abs_mean = None
        if ref_logp is not None and mt > 0:
            kl_abs_mean = float(
                (np.abs(old_logp - ref_logp) * loss_mask).sum() / mt
            )
        behav_imp_mean = None
        if prox_logp is not None and mt > 0:
            behav_imp_mean = float(
                (np.exp((prox_logp - old_logp) * loss_mask) * loss_mask).sum()
                / mt
            )
        aux = {
            "klv": klv,
            "n_seqs": len(layout),
            "loss_mask": loss_mask,
            "old_logp": old_logp,
            "ref_logp": ref_logp,
            "scores": scores,
            "no_eos": no_eos,
            "ref_kl": ref_kl,
            "kl_abs_mean": kl_abs_mean,
            "behav_imp_mean": behav_imp_mean,
            "degenerate_var": degenerate_var,
        }
        return train_sample, extra_keys, aux

    def train_step(
        self, model: Model, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        with tracer.span("ppo_prepare", cat="host"):
            train_sample, extra_keys, aux = self._prepare_train_sample(
                model, sample, mb_spec
            )
        loss_mask = aux["loss_mask"]
        old_logp, ref_logp = aux["old_logp"], aux["ref_logp"]

        verdict = self._batch_verdict(aux)
        if verdict:
            # Quarantine BEFORE any gradient dispatch: no minibatch of
            # this batch touches the optimizer; the master records a
            # skipped step (and escalates to rollback on a streak).
            integrity.record_anomaly(verdict)
            logger.warning(
                "batch sentinel quarantined train step: "
                f"{integrity.verdict_kinds(verdict)} "
                f"(kl_abs_mean={aux['kl_abs_mean']} "
                f"behav_imp_mean={aux['behav_imp_mean']} "
                f"degenerate_var={aux['degenerate_var']})"
            )
            model.inc_version()
            return {
                "anomaly_verdict": float(verdict),
                "quarantined": 1.0,
                "task_reward": float(aux["scores"].mean()),
                "no_eos_ratio": float(aux["no_eos"].mean()),
                "n_response_tokens": float(loss_mask.sum()),
                "kl_ctl_value": aux["klv"],
                "n_minibatches_skipped": float(
                    min(self.n_minibatches, train_sample.bs)
                ),
            }

        loss_fn = self._get_loss_fn()
        all_stats = []
        n_skipped = 0
        with tracer.span("mb_split", cat="host"):
            mbs_list = train_sample.split_balanced(
                min(self.n_minibatches, train_sample.bs)
            )
        for mi, mb in enumerate(mbs_list):
            stats = model.engine.train_batch(
                mb,
                mb_spec,
                loss_fn=loss_fn,
                loss_weight_fn=_mask_count,
                token_key="packed_input_ids",
                extra_keys=extra_keys,
                version_steps=model.version,
            )
            all_stats.append(stats)
            imp = stats.get("importance_weight", 1.0)
            akl = abs(stats.get("approx_kl", 0.0))
            if (
                self.early_stop_imp_ratio is not None
                and imp > self.early_stop_imp_ratio
            ) or (
                self.early_stop_kl is not None and akl > self.early_stop_kl
            ):
                n_skipped = len(mbs_list) - (mi + 1)
                logger.warning(
                    f"early stop after minibatch {mi + 1}/{len(mbs_list)}: "
                    f"importance_weight={imp:.3f} approx_kl={akl:.4f} "
                    f"(thresholds {self.early_stop_imp_ratio}/"
                    f"{self.early_stop_kl}); skipping {n_skipped} minibatches"
                )
                break
        model.inc_version()

        out = {
            k: float(np.mean([s[k] for s in all_stats]))
            for k in all_stats[0]
        }
        # Adaptive KL control: steer next step's coefficient by this
        # batch's measured policy↔ref KL (reference updates inside the loss
        # fn with the same post-reward timing, ppo_interface.py:105).
        # Under sharded dispatch ref_kl was already device-reduced above
        # (exact + identical on every member, so the controller cannot
        # drift across the SPMD group); the host formula here would be
        # understated ~1/n_shards by the zero-filled rows.
        ref_kl = aux["ref_kl"]
        if ref_kl is None:
            ref_kl = 0.0
            if ref_logp is not None and loss_mask.sum() > 0:
                ref_kl = float(
                    ((old_logp - ref_logp) * loss_mask).sum()
                    / loss_mask.sum()
                )
        if ref_logp is not None and loss_mask.sum() > 0:
            self._kl().update(ref_kl, n_steps=aux["n_seqs"])

        out.update(
            task_reward=float(aux["scores"].mean()),
            no_eos_ratio=float(aux["no_eos"].mean()),
            # advantage_abs arrives from the jitted loss stats (exact
            # under sharding); out already carries it.
            n_response_tokens=float(loss_mask.sum()),
            kl_ctl_value=aux["klv"],
            ref_kl=ref_kl,
            n_minibatches_skipped=float(n_skipped),
        )
        return out

    # ------------- streamed (pipeline-overlapped) train -------------

    def train_stream_begin(
        self, model: Model, mb_spec: MicroBatchSpec
    ) -> Dict:
        """Open a pipeline-overlapped train stream.

        Chunks arrive via `train_stream_chunk` as their rollout groups
        retire from generation; advantages (and their normalization
        moments) are computed chunk-locally and grads accumulate into
        the engine's donated sum.  The single optimizer step fires in
        `train_stream_end`.  Overlap-off (in-flight window = 1) never
        reaches this path — the master dispatches window-1 steps
        through the unchanged barrier `train_step`, which is the
        bit-exactness guarantee.
        """
        return {
            "engine": model.engine.train_stream_begin(),
            "chunk_stats": [],
            "kl_num": 0.0,
            "kl_den": 0.0,
            "n_seqs": 0,
            "score_sum": 0.0,
            "score_n": 0,
            "no_eos_sum": 0.0,
            "no_eos_n": 0,
            "resp_tokens": 0.0,
            "klv": self._kl().value,
            "stopped": False,
            "n_chunks_skipped": 0,
            # Batch-sentinel trip: stop accumulating AND force the
            # engine to discard the partial grad sum at stream end.
            "quarantine_verdict": 0,
        }

    def train_stream_chunk(
        self,
        model: Model,
        state: Dict,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
    ) -> Dict[str, float]:
        """Advantages + grad accumulation for one retired rollout chunk.

        Returns the chunk's stats in `*_denominator`-weighted form so
        `merge_stats` recovers the token-weighted step means even when
        chunks carry uneven token counts."""
        if state["stopped"]:
            state["n_chunks_skipped"] += 1
            return {"n_chunks_skipped": 1.0}
        if sample.shard_blocks() is not None:
            raise ValueError(
                "pipeline overlap does not compose with shard-exact "
                "dispatch; chunk inputs must be broadcast"
            )
        train_sample, extra_keys, aux = self._prepare_train_sample(
            model, sample, mb_spec
        )
        verdict = self._batch_verdict(aux)
        if verdict:
            # Sentinel tripped mid-stream: this chunk never reaches the
            # engine, later chunks short-circuit via `stopped`, and the
            # whole step's partial grad sum is discarded at stream end.
            state["stopped"] = True
            state["quarantine_verdict"] |= verdict
            state["n_chunks_skipped"] += 1
            integrity.record_anomaly(verdict)
            logger.warning(
                "batch sentinel quarantined stream chunk "
                f"{len(state['chunk_stats']) + 1}: "
                f"{integrity.verdict_kinds(verdict)}; the step's "
                "accumulated gradient will be discarded"
            )
            return {
                "n_chunks_skipped": 1.0,
                "anomaly_verdict": float(verdict),
            }
        raw = model.engine.train_stream_chunk(
            state["engine"],
            train_sample,
            mb_spec,
            loss_fn=self._get_loss_fn(),
            loss_weight_fn=_mask_count,
            token_key="packed_input_ids",
            extra_keys=extra_keys,
            version_steps=model.version,
        )
        w = max(raw.pop("chunk_weight"), 1.0)
        loss_sum = raw.pop("chunk_loss_sum")
        raw.pop("chunk_micro_batches", None)
        stats: Dict[str, float] = {
            "loss": loss_sum / w,
            "loss_denominator": w,
        }
        for k, v in raw.items():
            base = k[: -len("_sum")] if k.endswith("_sum") else k
            stats[base] = v / w
            stats[base + "_denominator"] = w

        loss_mask = aux["loss_mask"]
        old_logp, ref_logp = aux["old_logp"], aux["ref_logp"]
        mt = float(loss_mask.sum())
        if ref_logp is not None and mt > 0:
            state["kl_num"] += float(
                ((old_logp - ref_logp) * loss_mask).sum()
            )
            state["kl_den"] += mt
        state["n_seqs"] += aux["n_seqs"]
        state["score_sum"] += float(aux["scores"].sum())
        state["score_n"] += len(aux["scores"])
        state["no_eos_sum"] += float(aux["no_eos"].sum())
        state["no_eos_n"] += len(aux["no_eos"])
        state["resp_tokens"] += mt
        state["chunk_stats"].append(stats)

        imp = stats.get("importance_weight", 1.0)
        akl = abs(stats.get("approx_kl", 0.0))
        if (
            self.early_stop_imp_ratio is not None
            and imp > self.early_stop_imp_ratio
        ) or (self.early_stop_kl is not None and akl > self.early_stop_kl):
            state["stopped"] = True
            logger.warning(
                f"early stop after stream chunk "
                f"{len(state['chunk_stats'])}: importance_weight="
                f"{imp:.3f} approx_kl={akl:.4f} (thresholds "
                f"{self.early_stop_imp_ratio}/{self.early_stop_kl}); "
                f"remaining chunks accumulate no gradient"
            )
        return stats

    def train_stream_end(
        self, model: Model, state: Dict, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        """One optimizer step over the streamed grad sum + merged stats."""
        verdict = int(state["quarantine_verdict"])
        if verdict and state["engine"]["acc"] is None:
            # Sentinel tripped before any chunk reached the engine:
            # there is no grad sum to discard and no optimizer step.
            eng_out: Dict[str, float] = {
                "grad_norm": 0.0,
                "update_norm": 0.0,
                "n_micro_batches": 0.0,
                "n_stream_chunks": 0.0,
            }
        else:
            eng_out = model.engine.train_stream_end(
                state["engine"], quarantine=bool(verdict)
            )
        model.inc_version()
        out = (
            merge_stats(state["chunk_stats"]) if state["chunk_stats"] else {}
        )
        # The engine's stream totals are authoritative for the keys both
        # report (they agree up to float reassociation).
        out.update(eng_out)
        if verdict:
            out["anomaly_verdict"] = float(
                int(out.get("anomaly_verdict", 0.0)) | verdict
            )
            out["quarantined"] = 1.0
        ref_kl = 0.0
        if state["kl_den"] > 0:
            ref_kl = state["kl_num"] / state["kl_den"]
            self._kl().update(ref_kl, n_steps=state["n_seqs"])
        out.update(
            task_reward=state["score_sum"] / max(state["score_n"], 1),
            no_eos_ratio=state["no_eos_sum"] / max(state["no_eos_n"], 1),
            n_response_tokens=state["resp_tokens"],
            kl_ctl_value=state["klv"],
            ref_kl=ref_kl,
            n_minibatches_skipped=float(state["n_chunks_skipped"]),
        )
        return out

    _loss_fn_cache = None

    def _get_loss_fn(self):
        if self._loss_fn_cache is None:
            object.__setattr__(
                self,
                "_loss_fn_cache",
                _ppo_actor_loss_factory(
                    self.eps_clip, self.behav_imp_weight_cap
                ),
            )
        return self._loss_fn_cache

    def save(self, model: Model, save_dir: str) -> None:
        from areal_tpu.interfaces.sft import SFTInterface

        SFTInterface().save(model, save_dir)


@dataclasses.dataclass
class PPOCriticInterface(ModelInterface):
    n_minibatches: int = 4
    value_eps_clip: float = 0.2
    discount: float = 1.0
    gae_lambda: float = 1.0
    max_reward_clip: float = 5.0
    kl_ctl: float = 0.0
    # Running-mean/std normalization of returns (reference:
    # ppo_interface.py:175-210 + modules/rms.py): the critic head learns
    # normalized targets; predictions are denormalized before GAE.
    value_norm: bool = False
    value_norm_type: str = "exp"  # "exp" | "ma"
    value_norm_beta: float = 0.99995
    value_norm_eps: float = 1e-5

    def _rms(self):
        if getattr(self, "_rms_inst", None) is None:
            from areal_tpu.interfaces.value_norm import make_value_norm

            object.__setattr__(
                self,
                "_rms_inst",
                make_value_norm(
                    self.value_norm_type,
                    self.value_norm_beta,
                    self.value_norm_eps,
                ),
            )
        return self._rms_inst

    def state_dict(self) -> Dict[str, float]:
        # Running moments ride recover checkpoints: a restored critic head
        # (trained on normalized targets) must keep its statistics or
        # inference denormalizes with the identity.
        return self._rms().state_dict() if self.value_norm else {}

    def load_state_dict(self, sd) -> None:
        if self.value_norm and sd:
            self._rms().load_state_dict(sd)

    def save(self, model: Model, save_dir: str) -> None:
        # Critic checkpoints (incl. the trained value head) roundtrip via
        # the HF registry — without this, value-mode recover restores a
        # fresh critic (the bug the recover test pins down).
        from areal_tpu.interfaces.sft import SFTInterface

        SFTInterface().save(model, save_dir)

    def inference(
        self, model: Model, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        out = model.engine.forward(
            sample, mb_spec, post_fn=_value_post, output_key="values",
            token_key="packed_input_ids",
        )
        if self.value_norm:
            # Head outputs live in normalized-return space; hand real-scale
            # values to the consumers (actor GAE, our own train_step).
            out.data["values"] = self._rms().denormalize(
                np.asarray(out.data["values"], np.float32)
            )
        return out

    def _prepare_train_sample(
        self, model: Model, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        """KL-shaped rewards → GAE returns → (optional) value-norm →
        packed train sample.  Shared by the barrier `train_step` and the
        streamed `train_stream_chunk`; under streaming the value-norm
        running moments advance chunk-by-chunk (the streaming form of
        the running-statistics update)."""
        layout, _ = _extract_layout(sample)
        total = sum(L for (_, L, _) in layout)
        old_logp = _seq_align_minus1(sample, "packed_logprobs")
        ref_logp = (
            _seq_align_minus1(sample, "packed_ref_logprobs")
            if "packed_ref_logprobs" in sample.keys
            else None
        )
        values = np.asarray(sample.data["values"], np.float32)
        scores = np.clip(
            np.asarray(sample.data["rewards"], np.float32),
            -self.max_reward_clip,
            self.max_reward_clip,
        )
        no_eos = np.asarray(sample.data["seq_no_eos_mask"], np.float32)

        rewards = np.zeros(total, np.float32)
        loss_mask = np.zeros(total, np.float32)
        returns_full = np.zeros(total, np.float32)
        if ref_logp is not None and self.kl_ctl != 0.0:
            rewards -= self.kl_ctl * (old_logp - ref_logp)
        seq_slices = []
        for si, (s, L, pl) in enumerate(layout):
            lo, hi = s + max(pl - 1, 0), s + L - 1
            loss_mask[lo:hi] = 1.0
            if hi > lo:
                rewards[hi - 1] += scores[si]
            seq_slices.append((lo, hi))
        rewards *= loss_mask

        r_parts, v_parts, seg_parts, boot_parts = [], [], [], []
        for si, (lo, hi) in enumerate(seq_slices):
            n = hi - lo
            if n == 0:
                continue
            r_parts.append(rewards[lo:hi])
            v_parts.append(values[lo:hi])
            seg_parts.append(np.full(n, si + 1, np.int32))
            b = np.zeros(n, np.float32)
            b[-1] = no_eos[si] * values[layout[si][0] + layout[si][1] - 1]
            boot_parts.append(b)
        if r_parts:
            _, ret1 = gae_packed(
                jnp.asarray(np.concatenate(r_parts)),
                jnp.asarray(np.concatenate(v_parts)),
                jnp.asarray(np.concatenate(seg_parts)),
                jnp.asarray(np.concatenate(boot_parts)),
                self.discount,
                self.gae_lambda,
            )
            ret1 = np.asarray(ret1)
            off = 0
            for (lo, hi) in seq_slices:
                returns_full[lo:hi] = ret1[off : off + (hi - lo)]
                off += hi - lo

        if self.value_norm:
            # Update running moments with this batch's real-scale returns,
            # then train the head against NORMALIZED targets (old values
            # re-normalized so the clip window lives in the same space).
            # Sharded dispatch: host returns are garbage for other
            # members' rows (their `values` are zero-filled), so the
            # batch moments come from the exact in-mesh reduction —
            # identical on every member, keeping the running stats in
            # lockstep across the SPMD group.
            rms = self._rms()
            if sample.shard_blocks() is not None:
                probe = sample.select_keys({"packed_input_ids"})
                _add_aligned_keys(
                    probe,
                    {"ret_probe": returns_full, "loss_mask": loss_mask},
                )
                mom = model.engine.masked_moments(
                    probe, mb_spec, ("ret_probe",), mask_key="loss_mask"
                )
                cnt = mom["count"]
                if cnt > 0:
                    s, ssq, _ = mom["ret_probe"]
                    rms.update_moments(s / cnt, ssq / cnt, cnt)
            else:
                rms.update(returns_full, mask=loss_mask)
            returns_full = rms.normalize(returns_full)
            values = rms.normalize(values)

        train_sample = sample.select_keys({"packed_input_ids", "prompt_mask"})
        _add_aligned_keys(
            train_sample,
            {
                "old_values": values,
                "returns": returns_full,
                "loss_mask": loss_mask,
            },
        )
        return train_sample

    def train_step(
        self, model: Model, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        train_sample = self._prepare_train_sample(model, sample, mb_spec)
        loss_fn = self._get_loss_fn()
        all_stats = []
        for mb in train_sample.split_balanced(
            min(self.n_minibatches, train_sample.bs)
        ):
            stats = model.engine.train_batch(
                mb,
                mb_spec,
                loss_fn=loss_fn,
                loss_weight_fn=_mask_count,
                token_key="packed_input_ids",
                extra_keys=("old_values", "returns", "loss_mask"),
                version_steps=model.version,
            )
            all_stats.append(stats)
        model.inc_version()
        return {
            k: float(np.mean([s[k] for s in all_stats])) for k in all_stats[0]
        }

    # ------------- streamed (pipeline-overlapped) train -------------

    def train_stream_begin(
        self, model: Model, mb_spec: MicroBatchSpec
    ) -> Dict:
        return {
            "engine": model.engine.train_stream_begin(),
            "chunk_stats": [],
        }

    def train_stream_chunk(
        self,
        model: Model,
        state: Dict,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
    ) -> Dict[str, float]:
        if sample.shard_blocks() is not None:
            raise ValueError(
                "pipeline overlap does not compose with shard-exact "
                "dispatch; chunk inputs must be broadcast"
            )
        train_sample = self._prepare_train_sample(model, sample, mb_spec)
        raw = model.engine.train_stream_chunk(
            state["engine"],
            train_sample,
            mb_spec,
            loss_fn=self._get_loss_fn(),
            loss_weight_fn=_mask_count,
            token_key="packed_input_ids",
            extra_keys=("old_values", "returns", "loss_mask"),
            version_steps=model.version,
        )
        w = max(raw.pop("chunk_weight"), 1.0)
        loss_sum = raw.pop("chunk_loss_sum")
        raw.pop("chunk_micro_batches", None)
        stats: Dict[str, float] = {
            "loss": loss_sum / w,
            "loss_denominator": w,
        }
        for k, v in raw.items():
            base = k[: -len("_sum")] if k.endswith("_sum") else k
            stats[base] = v / w
            stats[base + "_denominator"] = w
        state["chunk_stats"].append(stats)
        return stats

    def train_stream_end(
        self, model: Model, state: Dict, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        eng_out = model.engine.train_stream_end(state["engine"])
        model.inc_version()
        out = (
            merge_stats(state["chunk_stats"]) if state["chunk_stats"] else {}
        )
        out.update(eng_out)
        return out

    _loss_fn_cache = None

    def _get_loss_fn(self):
        if self._loss_fn_cache is None:
            object.__setattr__(
                self,
                "_loss_fn_cache",
                _ppo_critic_loss_factory(self.value_eps_clip),
            )
        return self._loss_fn_cache


register_interface("ppo_actor", PPOActorInterface)
register_interface("ppo_critic", PPOCriticInterface)
