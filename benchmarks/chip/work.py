"""The work a verdict needs, reckoned from the BNN's shapes alone.

The same figures hold whatever implements the model (op-table rows,
XNOR-popcount words, an int8 matmul, a packed or unpacked wire format), so
no later change to the program can push a share of a peak past 100 %.

* Ops per packet: ``2 * sum(in * out)`` over the layers; a +-1
  multiply-accumulate counts as 2 ops.  HEADLINE (32-64-32): 8,192.
* Bytes per packet: the fewest a verdict needs, ``(input bits + output
  bits) / 8``.  HEADLINE: 8 B.
* Least time for ``n`` packets: ``max(bytes / HBM bandwidth, ops / int8
  peak)``; whichever term is larger bounds it.
"""
from __future__ import annotations

import numpy as np


def ops_per_packet(shape) -> int:
    """Operations one packet needs through a BNN of layer sizes ``shape``."""
    return int(sum(2 * a * b for a, b in zip(shape[:-1], shape[1:])))


def bytes_per_packet(shape) -> float:
    """Bytes one packet's header bits and verdict bits occupy, packed."""
    return (shape[0] + shape[-1]) / 8


def totals(shapes, packets_per_tenant) -> tuple[float, float]:
    """(ops, bytes) for ``packets_per_tenant[t]`` packets through tenant
    ``t``'s model of layer sizes ``shapes[t]``: each packet counts its own
    tenant's model."""
    counts = np.asarray(packets_per_tenant, np.float64)
    if counts.shape != (len(shapes),):
        raise ValueError(
            f"{counts.shape[0] if counts.ndim else 0} packet counts for "
            f"{len(shapes)} tenants"
        )
    ops = sum(c * ops_per_packet(s) for c, s in zip(counts, shapes))
    nbytes = sum(c * bytes_per_packet(s) for c, s in zip(counts, shapes))
    return float(ops), float(nbytes)


def least_time(ops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The least seconds the chip could take for this work, and which
    bound sets it (``"compute"`` or ``"memory"``)."""
    t_ops = ops / peaks.ops_int8
    t_bytes = nbytes / peaks.hbm_bw
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
