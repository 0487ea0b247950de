"""PR 58's reader in the harness's own cases, on `test_ssmd.py`'s recorded
toy run: the chunked scan's share of the gradient program
(`ssm_scan_train_share`: scope `layer/ssm/ssd_scan` under `train/grad`,
whatever runs under it — the `jnp` form's fusions at the parent, the kernels
`ssd_chunk_fwd` / `ssd_chunk_bwd` beside their slices after it), and that it
says nothing for a program without the scope, and for an untraced run.  The
reader has NO entry in BENCHMARK.json yet: `per_layer` holds the 128 entries
the contract allows (PERF.md section 7), and a PR that adds one may not take
one away."""
import pytest

from benchmark import files
from benchmark.metrics import ssm_scan_train_share, ssm_train_share_ssmd
from benchmark.tests.test_ssmd import POOL, SCOPES, _run

# The sweep's kernels lie UNDER `ssd_scan`: parent and change read one span.
KERNEL_SCOPES = {k: v for k, v in SCOPES.items() if "ssd_scan" not in k or (
    "train/grad" not in k)}
KERNEL_SCOPES.update({
    "train/grad/layer/ssm/ssd_scan": {"fwd": 0.02, "recompute": 0.02,
                                      "bwd": 0.04},
    "train/grad/layer/ssm/ssd_scan/ssd_chunk_fwd/ssd_chunk_fwd":
        {"fwd": 0.03, "recompute": 0.03},
    "train/grad/layer/ssm/ssd_scan/ssd_chunk_bwd/ssd_chunk_bwd":
        {"bwd": 0.06},
    "train/grad/layer/ssm/in_proj": {"fwd": 0.1, "recompute": 0.1,
                                     "bwd": 0.2},
})


def test_the_reader_sums_what_runs_under_ssd_scan_in_the_gradient_program():
    # 0.8 s of the scan beside 0.8 s of the MLPs: the serving chunk's own
    # `ssd_scan` (under `gen/`) is not counted.
    assert ssm_scan_train_share.read(_run(SCOPES, POOL)) == pytest.approx(50.0)
    # kernels and what is left around them, 0.2 of 0.2 + 0.4 + 0.8
    run = _run(KERNEL_SCOPES, POOL)
    assert ssm_scan_train_share.read(run) == pytest.approx(100 * 0.2 / 1.4)
    assert ssm_scan_train_share.read(run) < ssm_train_share_ssmd.read(run)


def test_the_reader_says_nothing_without_the_scope_or_a_trace():
    bare = {k: v for k, v in SCOPES.items() if "layer/ssm" not in k}
    assert ssm_scan_train_share.read(_run(bare, POOL)) is None
    assert ssm_scan_train_share.read(_run(None, POOL)) is None


def test_per_layer_is_full_and_the_reader_waits_for_room():
    spec = files.benchmark_json()
    assert len(spec["per_layer"]) == 128
    assert "ssm_scan_train_share" not in {
        m["name"] for m in spec["per_layer"]}
