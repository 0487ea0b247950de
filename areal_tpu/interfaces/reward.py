"""Multi-task reward interface: verification-based rewards for math & code.

Capability parity: realhf/impl/model/interface/math_rw_interface.py
(`MultiTaskRewardInterface`, registered "rw-math-code") + the local
verification paths of realhf/functioncall/.  Dispatches each sequence by its
task metadata, decodes the response, verifies, and emits ±`reward_value`
scores (one scalar per sequence, the reference's reward layout).
"""

import dataclasses
import json
import os
import sys
import tempfile
from typing import Any, Dict, List, Optional

import numpy as np

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import logging, tracer
from areal_tpu.api.model_api import Model, ModelInterface, register_interface

logger = logging.getLogger("reward")


def _row_is_choice(info: Dict[str, Any]) -> Optional[bool]:
    """Row-level multiple-choice evidence for is_multi_choice gating:
    an explicit flag or a rendered `choices` list decides; absent both,
    None lets the gold-string inference stand (rows without metadata
    must keep grading letter golds)."""
    if info.get("is_choice") is not None:
        return bool(info["is_choice"])
    if "choices" in info and info["choices"] is not None:
        return bool(info["choices"])
    return None


@dataclasses.dataclass
class MultiTaskRewardInterface(ModelInterface):
    """id2info maps query_id -> row dict with task/solutions/input_output
    (loaded from the dataset jsonl, reference math_code_dataset.load_metadata)."""

    id2info: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)
    dataset_path: Optional[str] = None
    reward_value: float = 5.0
    code_timeout_s: float = 8.0
    # http://host:port of a reward_service.py deployment; verification is
    # batched to it (local fallback on failure).  None = grade in-process.
    remote_url: Optional[str] = None
    # Generous default: code batches can run minutes of sandboxed tests.
    remote_timeout_s: float = 600.0
    # When set, overrides every row's task key — forces one verifier
    # backend (e.g. "judge") for the whole run regardless of dataset
    # metadata.  "" = dispatch per-row.
    reward_backend: str = ""
    # Route grading through the announced verifier fleet
    # (system/verifier_pool.py) instead of a fixed remote_url: batches
    # load-balance across live workers with per-server breakers and
    # retry-to-a-different-server, degrading to the in-process registry
    # when no worker is live.  Takes precedence over remote_url.
    verifier_pool: bool = False
    pool_experiment: str = ""
    pool_trial: str = ""
    pool_attempt_timeout_s: float = 60.0
    _pool: Any = dataclasses.field(default=None, init=False, repr=False)

    def __post_init__(self):
        if self.dataset_path and not self.id2info:
            with open(self.dataset_path) as f:
                for line in f:
                    if not line.strip():
                        continue
                    row = json.loads(line)
                    row.setdefault("task", "math")
                    self.id2info[str(row.get("query_id", row.get("id")))] = row

    def inference(
        self, model: Optional[Model], sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        """Scores every sequence; returns key 'rewards' (1 scalar/seq).

        `model` supplies the tokenizer; no forward pass happens (the
        reference's rw interface also runs verification, not a model)."""
        tokenizer = model.tokenizer if model is not None else None
        assert tokenizer is not None, "reward interface needs a tokenizer"
        seqlens_r: List[List[int]] = []
        with tracer.span("reward_decode", cat="host"):
            todo = self._decode_responses(sample, tokenizer, seqlens_r)
        with tracer.span("reward_verify", cat="host", n=len(todo)):
            oks = self._verify_all(todo)
        n_correct = sum(map(int, oks))
        rewards = [
            self.reward_value if ok else -self.reward_value for ok in oks
        ]
        logger.info(
            f"reward verification: {n_correct}/{len(rewards)} correct"
        )
        return SequenceSample(
            keys={"rewards"},
            ids=list(sample.ids),
            seqlens={"rewards": seqlens_r},
            data={"rewards": np.asarray(rewards, np.float32)},
            metadata={},
        )

    def _decode_responses(
        self, sample: SequenceSample, tokenizer, seqlens_r: List[List[int]]
    ) -> List[Dict[str, Any]]:
        """One verifier item per sequence (its response's text and the
        row's payload); appends each group's reward lengths to
        `seqlens_r`."""
        tokens = np.asarray(sample.data["packed_input_ids"])
        pmask = np.asarray(sample.data["prompt_mask"])
        bounds = sample.cu_seqlens("packed_input_ids")
        todo: List[Dict[str, Any]] = []
        si = 0
        for ei, group in enumerate(sample.seqlens["packed_input_ids"]):
            qid = str(sample.ids[ei])
            info = self.id2info.get(qid, {})
            task = info.get("task", "math")
            seqlens_r.append([1] * len(group))
            for _ in group:
                lo, hi = bounds[si], bounds[si + 1]
                resp_tokens = tokens[lo:hi][~pmask[lo:hi].astype(bool)]
                text = tokenizer.decode(resp_tokens.tolist())
                todo.append(
                    {
                        "task": self.reward_backend or task,
                        "text": text,
                        # Opaque backend payload (reward_service registry
                        # schema): backends read it verbatim, so adding a
                        # backend never remaps keys here.
                        "payload": {
                            "solutions": info.get("solutions") or [],
                            "input_output": info.get("input_output"),
                            "choices": info.get("choices"),
                            "reference": info.get("reference"),
                            "timeout_s": self.code_timeout_s,
                        },
                    }
                )
                si += 1
        return todo

    def _verify_all(self, todo: List[Dict[str, Any]]) -> List[bool]:
        """Dispatch to the configured verifier and wait for every verdict."""
        if self.verifier_pool:
            return self._verifier_pool().verify_batch(todo)
        if self.remote_url:
            from areal_tpu.interfaces.reward_service import RemoteVerifier

            return RemoteVerifier(
                self.remote_url, timeout_s=self.remote_timeout_s
            ).verify_batch(todo)
        return [
            self.verify(it["task"], it["text"], it["payload"])
            for it in todo
        ]

    def _verifier_pool(self):
        """Lazily build (and cache) the fleet-discovering pool client —
        one client per interface, so breaker state and membership view
        survive across inference calls."""
        if self._pool is None:
            from areal_tpu.system.verifier_pool import (
                VerifierPool, verifier_discovery,
            )

            if not (self.pool_experiment and self.pool_trial):
                raise ValueError(
                    "verifier_pool=True needs pool_experiment and "
                    "pool_trial to discover the announced fleet"
                )
            self._pool = VerifierPool(
                discovery=verifier_discovery(
                    self.pool_experiment, self.pool_trial
                ),
                attempt_timeout_s=self.pool_attempt_timeout_s,
            )
        return self._pool

    def verify(self, task: str, text: str, info: Dict[str, Any]) -> bool:
        """Grade one response for ``task`` via the verifier-backend
        registry (reward_service) — public so the offline evaluator
        shares the exact training-reward graders, and so a backend
        registered once is available to every grading path."""
        from areal_tpu.interfaces import reward_service

        payload = dict(info)
        payload.setdefault("timeout_s", self.code_timeout_s)
        return reward_service.grade_item(
            {
                "task": self.reward_backend or task,
                "text": text,
                "payload": payload,
            }
        )

    # -- code verification: run extracted program against input/output pairs
    # in a SANDBOXED subprocess — rlimits + tmpdir jail + (where available)
    # a network namespace; see interfaces/sandbox.py for the trust model
    # (reference: functioncall/code/local_verify, whose hostile-code path
    # is the remote FaaS sandbox like our reward_service).
    def _verify_code(self, text: str, info: Dict[str, Any]) -> bool:
        from areal_tpu.interfaces.sandbox import run_sandboxed

        m = _extract_code_block(text)
        if m is None:
            return False
        try:
            io_spec = info.get("input_output")
            io_spec = json.loads(io_spec) if isinstance(io_spec, str) else io_spec
            inputs, outputs = io_spec["inputs"], io_spec["outputs"]
        except (KeyError, TypeError, json.JSONDecodeError):
            return False
        with tempfile.TemporaryDirectory(prefix="areal_grade_") as jail:
            path = os.path.join(jail, "prog.py")
            with open(path, "w") as f:
                f.write(m)
            for inp, expected in zip(inputs, outputs):
                rc, stdout = run_sandboxed(
                    [sys.executable, path],
                    input_text=inp,
                    timeout_s=self.code_timeout_s,
                    cwd=jail,
                )
                if rc != 0 or stdout.strip() != expected.strip():
                    return False
        return True


def _extract_code_block(text: str) -> Optional[str]:
    import re

    blocks = re.findall(r"```(?:python)?\n(.*?)```", text, flags=re.DOTALL)
    return blocks[-1] if blocks else None


register_interface("rw-math-code", MultiTaskRewardInterface)
