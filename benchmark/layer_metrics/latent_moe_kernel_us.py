"""Kernels: device time of ONE `moe_gmm` call of the decode program where
the experts work in a latent and have no gate (Nemotron-H: the up call
[M, 1024] x [1024, 2688] with the squared ReLU in its body, then the down
call; two a block, ten a token step at five expert blocks): half of what
`moe_gmm_kernel_us.py` reads for a layer's two calls together, from the same
events. (That reader would read this cell as it is; its `workloads` list is
held to its one cell by `test_bench_sdar_moe.py`, so this cell reports the
call under a name of its own.)"""

import os

from benchmark.manifest import _load_py


def read(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    us = _load_py(os.path.join(here, "moe_gmm_kernel_us.py"),
                  "_bench_metric_moe_gmm_kernel_us").read(obs)
    return None if us is None else us / 2
