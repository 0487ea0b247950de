"""Remote reward verification service (functioncall FaaS parity):
server round-trip, local fallback, the verifier-backend registry with
its opaque {task, text, payload} schema, and the reward interface's
remote path."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from areal_tpu.interfaces import reward_service
from areal_tpu.interfaces.reward_service import (
    RemoteVerifier,
    grade_item,
    register_verifier,
    serve,
    verifier_names,
)


@pytest.fixture(scope="module")
def server():
    srv = serve("127.0.0.1", 0, background=True)
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()


# Every client of the live fixture is held to this: one attempt of a
# few tens of seconds (a loaded host grades the three items below in
# one), where the client's own default is three of 600 s.  A service
# that cannot answer then costs its case this bound, and the case says
# so: `_remote_errors` moves whenever a round trip failed, which the
# verdicts alone cannot show (the local fallback gives the same ones).
BOUND_S = 30.0


def _bounded(url):
    return RemoteVerifier(url, timeout_s=BOUND_S, attempts=1)


def _remote_errors():
    return sum(
        reward_service._M_REMOTE_ERRORS.labels(reason).get()
        for reason in ("shape", "http", "timeout", "network", "protocol")
    )


ROUNDTRIP_ITEMS = [
    {"task": "math", "text": r"the answer is \boxed{\frac{1}{2}}",
     "solutions": [r"\boxed{0.5}"]},
    {"task": "math", "text": r"\boxed{3}", "solutions": [r"\boxed{4}"]},
    {"task": "code",
     "text": "```python\nprint(input())\n```",
     "input_output": json.dumps(
         {"inputs": ["hi"], "outputs": ["hi"]}
     )},
]


def _health_and_verify_roundtrip(server, verifier):
    with urllib.request.urlopen(server + "/health", timeout=5) as r:
        assert json.loads(r.read())["status"] == "ok"
    before = _remote_errors()
    got = verifier.verify_batch(ROUNDTRIP_ITEMS)
    assert _remote_errors() == before, "graded by the fallback, not the service"
    assert got == [True, False, True]


def test_health_and_verify_roundtrip(server):
    _health_and_verify_roundtrip(server, _bounded(server))


def test_a_service_that_cannot_answer_fails_the_roundtrip_in_its_bound(
    server,
):
    """The round trip above against a backend that never returns: the
    client gives up after its one bounded attempt, grades locally (the
    fallback users depend on), and the case's own assertion names it.
    With the client's defaults this took 600 s and passed."""
    release = threading.Event()

    def stuck_in_the_service(text, payload):
        # The service grades on its pool's threads, the client's
        # fallback on the thread of the test.
        if threading.current_thread() is not threading.main_thread():
            release.wait()
        return reward_service._verify_code_backend(text, payload)

    register_verifier("code", stuck_in_the_service)
    try:
        t0 = time.monotonic()
        with pytest.raises(AssertionError, match="fallback"):
            _health_and_verify_roundtrip(
                server, RemoteVerifier(server, timeout_s=1.0, attempts=1)
            )
        assert time.monotonic() - t0 < BOUND_S
    finally:
        release.set()
        register_verifier("code", reward_service._verify_code_backend)


def test_local_fallback_on_dead_service():
    v = RemoteVerifier("http://127.0.0.1:1", timeout_s=0.5)
    items = [
        {"task": "math", "text": r"\boxed{7}", "solutions": [r"\boxed{7}"]}
    ]
    assert v.verify_batch(items) == [True]


class TestVerifierRegistry:
    """The pluggable reward fabric: grading dispatches on the item's
    `task` key over an open registry, payloads travel opaquely, and the
    pre-registry flat schema stays accepted for one release."""

    def test_builtin_backends_registered(self):
        names = verifier_names()
        for task in ("math", "code", "judge"):
            assert task in names

    def test_opaque_schema_dispatch(self):
        assert grade_item({
            "task": "math", "text": r"\boxed{7}",
            "payload": {"solutions": [r"\boxed{7}"]},
        }) is True
        assert grade_item({
            "task": "judge", "text": "I conclude the answer is Paris.",
            "payload": {"reference": "paris"},
        }) is True
        assert grade_item({
            "task": "judge", "text": "I conclude the answer is Lyon.",
            "payload": {"reference": "paris"},
        }) is False

    def test_judge_tail_window(self):
        item = {
            "task": "judge",
            "text": "paris? no wait. " + "x" * 64 + " the answer: Lyon",
            "payload": {"reference": "paris", "tail_chars": 32},
        }
        assert grade_item(item) is False  # match is outside the tail
        item["payload"]["tail_chars"] = 0
        assert grade_item(item) is True

    def test_custom_backend_round_trips_the_service(self, server):
        """A newly registered backend works end-to-end through the FaaS
        without any schema change — the server never interprets payload."""
        seen = {}

        def exact(text, payload):
            seen[payload.get("expect")] = payload
            return text == payload.get("expect")

        register_verifier("exact", exact)
        try:
            got = _bounded(server).verify_batch([
                {"task": "exact", "text": "abc",
                 "payload": {"expect": "abc", "nested": {"k": [1, 2]}}},
                {"task": "exact", "text": "abc",
                 "payload": {"expect": "xyz"}},
            ])
            assert got == [True, False]
            assert seen["abc"]["nested"] == {"k": [1, 2]}
        finally:
            reward_service._VERIFIERS.pop("exact", None)

    @pytest.fixture()
    def service_log(self, caplog):
        """The repo's logging module sets propagate=False, so caplog only
        sees records if its handler is attached to the logger directly."""
        import logging as _logging

        slog = _logging.getLogger("areal_tpu.reward_service")
        slog.addHandler(caplog.handler)
        try:
            with caplog.at_level(
                _logging.WARNING, logger="areal_tpu.reward_service"
            ):
                yield caplog
        finally:
            slog.removeHandler(caplog.handler)

    def test_unknown_task_grades_false_and_warns_once(self, service_log):
        reward_service._unknown_tasks_warned.discard("no-such-task")
        assert grade_item({"task": "no-such-task", "text": "x",
                           "payload": {}}) is False
        assert grade_item({"task": "no-such-task", "text": "x",
                           "payload": {}}) is False
        hits = [r for r in service_log.records
                if "no verifier backend" in r.getMessage()]
        assert len(hits) == 1

    def test_legacy_flat_schema_accepted_with_one_warning(self, service_log):
        reward_service._legacy_schema_warned = False
        try:
            assert grade_item({
                "task": "math", "text": r"\boxed{2}",
                "solutions": [r"\boxed{2}"],
            }) is True
            assert grade_item({
                "task": "math", "text": r"\boxed{2}",
                "solutions": [r"\boxed{3}"],
            }) is False
            hits = [r for r in service_log.records
                    if "legacy flat" in r.getMessage()]
            assert len(hits) == 1
        finally:
            reward_service._legacy_schema_warned = True


def test_reward_interface_remote_path(server):
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import Model
    from areal_tpu.interfaces.reward import MultiTaskRewardInterface
    from tests import fixtures

    tok = fixtures.make_tokenizer()
    right = tok.encode(r"\boxed{4}")
    wrong = tok.encode(r"\boxed{5}")
    parts = []
    for qid, resp in [("q0", right), ("q1", wrong)]:
        toks = np.asarray(list(resp), np.int32)
        parts.append(
            SequenceSample(
                keys={"packed_input_ids", "prompt_mask"},
                ids=[qid],
                seqlens={
                    "packed_input_ids": [[len(toks)]],
                    "prompt_mask": [[len(toks)]],
                },
                data={
                    "packed_input_ids": toks,
                    "prompt_mask": np.zeros(len(toks), bool),
                },
            )
        )
    sample = SequenceSample.gather(parts)
    iface = MultiTaskRewardInterface(
        id2info={
            "q0": {"task": "math", "solutions": [r"\boxed{4}"]},
            "q1": {"task": "math", "solutions": [r"\boxed{4}"]},
        },
        remote_url=server,
        remote_timeout_s=BOUND_S,
    )
    model = Model("reward", engine=None, tokenizer=tok, config=None)
    out = iface.inference(model, sample, MicroBatchSpec())
    r = np.asarray(out.data["rewards"])
    assert r[0] > 0 and r[1] < 0
