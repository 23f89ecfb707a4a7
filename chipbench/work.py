"""Work counts and peaks: the yardstick of the roofline shares.

The work of a Wilson kernel is counted in hop blocks.  One hop block
(``H_eo`` or ``H_oe`` on ``nrhs`` columns) must at least read every link
once (4 V links of 18 reals) and read and write V/2 spinors of 24 reals
per column, whatever kernel does it.  So the count depends only on the
extents, ``nrhs`` and the dtype, not on the kernel's variant or codec.
Its arithmetic intensity, 1320 flop per output site and column over
those bytes, is 1.7 flop/B at ``nrhs`` 1 and below 7 flop/B at any
``nrhs``, far under a v5e's ridge of 240 flop/B: the kernels are bound
by HBM bandwidth, and their roofline share is taken against it.

``kernels/<name>.json`` gives the hop blocks of one call of the kernel
named ``<name>``; ``peaks.json`` the peaks of each ``device_kind``.  A
kernel or a device that has no entry is an error, never a zero.
"""
from __future__ import annotations

import json
import math
import pathlib

HERE = pathlib.Path(__file__).resolve().parent

HOP_FLOPS_PER_SITE = 1320
LINK_REALS = 18
SPINOR_REALS = 24


def hop_block_bytes(extents, nrhs: int, itemsize: int) -> int:
    """Least HBM bytes of one hop block on ``nrhs`` columns."""
    V = math.prod(int(e) for e in extents)
    return itemsize * (4 * V * LINK_REALS + nrhs * V * SPINOR_REALS)


def hop_block_flops(extents, nrhs: int) -> int:
    V = math.prod(int(e) for e in extents)
    return HOP_FLOPS_PER_SITE * (V // 2) * nrhs


def kernel_hop_blocks(name: str, root: pathlib.Path = HERE) -> float:
    """Hop blocks done by one call of the kernel ``name``."""
    path = root / "kernels" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"kernel {name!r} has no work entry {path}")
    return float(json.loads(path.read_text())["hop_blocks"])


def peaks(device_kind: str, root: pathlib.Path = HERE) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``."""
    table = json.loads((root / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
