"""The least work a batched record plan needs, counted from the query.

The count depends on the plan and the relations it crosses, not on how a
kernel lays them out, so no implementation can read above its roofline:

- bytes: the packed probe masks read and the packed answers written, plus,
  for each hop, the smaller of the relation's dense bit matrix
  (``n_in * n_out / 8``) and its index arrays (int32 offsets per output
  row and one int32 per nonzero);
- operations: one per probe and nonzero of each hop (``B * nnz``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

Hop = Tuple[int, int, int]    # (n_in, n_out, nnz) in the walked direction


def packed_bytes(batch: int, n: int) -> int:
    return batch * (-(-n // 32)) * 4


def dense_bytes(n_in: int, n_out: int) -> int:
    return -(-n_in * n_out // 8)


def index_bytes(n_out: int, nnz: int) -> int:
    return 4 * (n_out + 1) + 4 * nnz


@dataclasses.dataclass(frozen=True)
class Work:
    bytes: int
    ops: int

    def least_seconds(self, peaks: dict) -> Tuple[float, str]:
        """The least time on a chip with these peaks, and which bound
        binds (``"memory"`` or ``"compute"``)."""
        t_mem = self.bytes / peaks["hbm_bytes_per_s"]
        t_ops = self.ops / peaks["int8_ops_per_s"]
        return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


def plan_work(batch: int, hops: Sequence[Hop]) -> Work:
    """Least bytes and operations of one ``batch``-probe plan over
    ``hops`` (in the order walked)."""
    total = packed_bytes(batch, hops[0][0]) + packed_bytes(batch, hops[-1][1])
    ops = 0
    for n_in, n_out, nnz in hops:
        total += min(dense_bytes(n_in, n_out), index_bytes(n_out, nnz))
        ops += batch * nnz
    return Work(total, ops)


def lineage_hops(n_source: int, row_steps, direction: str) -> List[Hop]:
    """The hops of a pipeline of row-preserving and row-filtering steps,
    from its row maps: a filter keeps ``len(kept)`` of its rows, every
    other step maps each row to itself."""
    hops: List[Hop] = []
    n = n_source
    for kept in row_steps:
        n_out = n if kept is None else len(kept)
        hops.append((n, n_out, n_out))
        n = n_out
    if direction == "bwd":
        hops = [(o, i, z) for i, o, z in reversed(hops)]
    return hops
