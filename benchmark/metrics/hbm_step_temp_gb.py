"""The `temp` row of the account of the process's peak (`hbm/temp_gb`):
the largest declared temporaries among the programs loaded inside the
interval in which the warm-up step's peak was set (else among those the
peak's request ran), and no more than the peak stands over what was in
use when that interval began -- a program's temporaries that lie in the
TPU runtime's reserve are not in the peak.  0.0 where the peak was set
between requests.  In GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "temp_gb")
