"""Kernels: the pages a decode step's sparse layers walked as a share of the
pages their rows held: the sum of `pages_selected` over the sum of
`pages_visible` of the program's `ray_tpu.engine.emit` spans in the slice
(both counted inside the decode program, from the lists `select_pages` hands
the kernel: the entries in use a row and KV head, and ceil(length / page) a
row and KV head; they reach the host behind the window's tokens). 100 while
every row is shorter than `dense_len`; 64 / ceil(length / 64) of a row past
it. The guard that the walk is the chosen pages and no more. None where the
program's spans carry no such counter."""

import os

from benchmark.manifest import _load_py


def read(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    counted = _load_py(os.path.join(here, "sparse_decode_kernel_us.py"),
                       "_bench_metric_sparse_decode_kernel_us").pages(obs)
    if not counted or not counted[1]:
        return None
    return 100.0 * counted[0] / counted[1]
