"""`ssm_train_share` for Mamba-2 mixers in two-branch layers: device
seconds of the gradient program under `layer/ssm` over all of
`train/grad`'s, in %."""
from benchmark.metrics import _ssmd, ssm_train_share


def read(run):
    return ssm_train_share.read(run) if _ssmd.is_ssmd(run) else None
