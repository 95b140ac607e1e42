"""Bytes that a kernel call must move, from its shapes.

The Pallas pack kernel (``hash_partition_pack``: hash, mask, block-local
rank and per-block histogram of one chunk of a shuffle's rows) reads the
keys and the validity as int32 ``[nb, block]`` tiles and writes the
destination and the rank alike, plus an int32 histogram ``[nb, bins]`` with
the ``partitions + 1`` bins padded to whole 128-lane tiles.  ``nb`` is the
chunk's rows, padded to a whole block, in blocks, padded to 8 sublanes.

:func:`pack_calls` lists the calls one request of a template makes on each
chip, from the engine's physical plan: every shuffle edge packs its input
rows per chip in ``pipeline_chunks`` equal chunks (unchunked where they do
not divide).  A one-chip mesh elides the exchange and makes none.
"""

from __future__ import annotations

import dataclasses

BLOCK = 256  # rows per block row of the kernel
SUBLANES = 8
LANES = 128
WORD = 4  # int32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class PackCall:
    rows: int  # rows of the chunk handed to the kernel, per chip
    partitions: int  # destinations (chips on the shuffle axis)

    @property
    def tiles(self) -> int:
        """Block rows the kernel's grid covers."""
        return _round_up(_round_up(self.rows, BLOCK) // BLOCK, SUBLANES)

    @property
    def bytes(self) -> int:
        """HBM bytes read and written: keys, valid, dest, rank, histogram."""
        bins = _round_up(self.partitions + 1, LANES)
        return WORD * self.tiles * (4 * BLOCK + bins)


def plan_pack_calls(plan, pack_impl: str, pipeline_chunks: int) -> list[PackCall]:
    """The pack kernel calls one run of ``plan`` makes on each chip."""
    if pack_impl != "pallas" or plan.num_shards == 1 or plan.num_pods != 1:
        return []
    calls: list[PackCall] = []
    seen: set[int] = set()

    def walk(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        if node.kind == "exchange" and node.info["exkind"] == "shuffle":
            rows = node.children[0].cap
            chunks = pipeline_chunks if rows % pipeline_chunks == 0 else 1
            calls.extend([PackCall(rows // chunks, plan.num_shards)] * chunks)
        for child in node.children:
            walk(child)

    walk(plan.root)
    return calls


def pack_calls(engine, templates) -> dict[str, list[PackCall]]:
    """``{template: calls}`` for the templates an engine serves (after its
    first request, so its shared multiplexer exists)."""
    mux = engine._mux
    return {
        pq.name: plan_pack_calls(
            engine._plan_for(pq)[0], mux.pack_impl, mux.pipeline_chunks
        )
        for pq in templates
    }
