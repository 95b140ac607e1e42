"""Roofline term extraction from a compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds (EXPERIMENTS.md §Roofline):

* compute    = HLO_FLOPs / peak_FLOP/s          (per-chip program)
* memory     = HLO_bytes / HBM_bw
* collective = Σ per-op bytes / link_bw, split by network level:
  in-pod collectives ride ICI (~50 GB/s/link), cross-pod ride DCI.

``cost_analysis()`` supplies FLOPs/bytes of the per-device partitioned
program.  Collective bytes are NOT in cost_analysis: we parse the optimized
post-SPMD HLO (``compiled.as_text()``) and sum the result-shape bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction, tagging each with its replica-group axis to
decide which network it crosses.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

from repro.core.topology import ChipSpec, chip_spec

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|((?:[a-z0-9]+)\[[^\]]*\](?:\{[^}]*\})?))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_split(hlo_text: str) -> tuple[dict[str, int], dict[str, int]]:
    """(total, async) per-op-kind result bytes of the per-device HLO.

    ``-start``/``-done`` async pairs are counted once (on the start) and
    additionally tallied in the *async* dict: those are the collectives the
    latency-hiding scheduler may overlap with compute, which is what the
    overlap-fraction audit measures.  Plain (synchronous) collectives only
    appear in the total.
    """
    total: dict[str, int] = {}
    async_: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async completion: counted at -start
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        shape_txt = m.group(1) or m.group(2)
        kind = m.group(3)
        b = _shape_bytes(shape_txt)
        total[kind] = total.get(kind, 0) + b
        if m.group(4) == "-start":
            async_[kind] = async_.get(kind, 0) + b
    return total, async_


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-op-kind result bytes of every collective in the per-device HLO.

    ``-start``/``-done`` async pairs are counted once (on the start).
    """
    return collective_bytes_split(hlo_text)[0]


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: dict[str, int]
    model_flops_global: float  # 6*N*D (or 6*N_active*D)
    chips: int
    chip: ChipSpec  # the chip the terms are priced for
    ideal_bytes_global: float = 0.0  # mandatory HBM traffic of a perfect impl
    # Subset of coll_bytes_per_chip issued as async -start/-done pairs (the
    # collectives the latency-hiding scheduler is free to overlap).
    async_coll_bytes_per_chip: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.flops_per_chip / self.chip.peak_flops_bf16

    @property
    def memory_s(self) -> float:
        return self.bytes_per_chip / self.chip.hbm_bandwidth

    @property
    def collective_s(self) -> float:
        total = sum(self.coll_bytes_per_chip.values())
        return total / self.chip.ici_link_bandwidth

    @property
    def async_collective_s(self) -> float:
        total = sum(self.async_coll_bytes_per_chip.values())
        return total / self.chip.ici_link_bandwidth

    @property
    def overlap_fraction(self) -> float:
        """Fraction of collective time hideable behind compute.

        Only async (-start/-done) collectives can overlap; of those, at
        most ``compute_s`` worth can actually hide.  0 when the program
        has no collectives at all.
        """
        if self.collective_s <= 0.0:
            return 0.0
        hidden = min(self.compute_s, self.async_collective_s)
        return hidden / self.collective_s

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        hlo_global = self.flops_per_chip * self.chips
        return self.model_flops_global / hlo_global if hlo_global else float("nan")

    @property
    def ideal_s(self) -> float:
        """Time a perfect implementation needs on this hardware.

        max(useful-FLOPs / peak, mandatory-HBM-bytes / bw): training at 4k
        is compute-ideal; decode is bandwidth-ideal (must read the weights
        and the KV cache once per token no matter what).
        """
        ideal_c = self.model_flops_global / self.chips / self.chip.peak_flops_bf16
        ideal_m = self.ideal_bytes_global / self.chips / self.chip.hbm_bandwidth
        return max(ideal_c, ideal_m)

    @property
    def roofline_fraction(self) -> float:
        """ideal time / modeled bound time (the score axis)."""
        return self.ideal_s / self.bound_s if self.bound_s else float("nan")

    def row(self) -> dict[str, Any]:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops_global,
            "ideal_s": self.ideal_s,
            "hlo_flops_per_chip": self.flops_per_chip,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
            "collective_breakdown": self.coll_bytes_per_chip,
            "async_collective_s": self.async_collective_s,
            "overlap_fraction": self.overlap_fraction,
        }


def model_flops(cfg, shape, n_params_active: int) -> float:
    """MODEL_FLOPS: 6*N*D for train, 2*N*D for prefill, 2*N*B for decode
    (D = tokens processed by the step; MoE uses N_active)."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_params_active * B * S
    if shape.kind == "prefill":
        return 2.0 * n_params_active * B * S
    # decode: one token per stream
    return 2.0 * n_params_active * B


def _cache_bytes(cfg, shape) -> float:
    """KV/state cache footprint (bf16 kv, f32 ssm states) for decode cells."""
    B, S = shape.global_batch, shape.seq_len
    bytes_ = 0.0
    if cfg.family in ("ssm", "hybrid"):
        d_inner = cfg.ssm_expand * cfg.d_model
        H = d_inner // cfg.ssm_head_dim
        n_mamba = cfg.num_layers
        bytes_ += n_mamba * B * H * cfg.ssm_head_dim * cfg.ssm_state * 4
        if cfg.family == "hybrid" and cfg.attn_every:
            n_attn = cfg.num_layers // cfg.attn_every
            bytes_ += n_attn * B * S * 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 2
        return bytes_
    if cfg.attn_kind == "mla":
        per_tok = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return cfg.num_layers * B * S * per_tok * 2
    layers = cfg.num_layers * (2 if cfg.is_encoder_decoder else 1)
    return layers * B * S * 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 2


def ideal_memory_bytes(cfg, shape, n_active: int, n_total: int, microbatches: int = 1) -> float:
    """Mandatory HBM traffic of a perfect implementation (global, bytes).

    train:   each microbatch makes fwd + bwd passes -> ~3 reads of the bf16
             params per microbatch (all experts are touched by a big batch),
             + one optimizer pass over f32 master/moments/grads (~20 B/param).
    prefill: one bf16 read of all params + one write of the cache.
    decode:  bf16 read of the params actually activated by the B streams
             (capped at all params) + one read of the cache.
    """
    if shape.kind == "train":
        return microbatches * 3.0 * 2.0 * n_total + 20.0 * n_total
    if shape.kind == "prefill":
        return 2.0 * n_total + _cache_bytes(cfg, shape)
    B = shape.global_batch
    return 2.0 * min(n_total, B * n_active) + _cache_bytes(cfg, shape)


def from_artifact(art: dict) -> RooflineTerms:
    """Terms of a dry-run artifact, priced for the chip its ``device_kind``
    names (an unknown kind raises)."""
    return RooflineTerms(
        arch=art["arch"],
        shape=art["shape"],
        mesh=art["mesh"],
        flops_per_chip=art["cost_analysis"].get("flops", 0.0),
        bytes_per_chip=art["cost_analysis"].get("bytes accessed", 0.0),
        coll_bytes_per_chip=art["collective_bytes"],
        model_flops_global=art["model_flops"],
        chips=art["chips"],
        chip=chip_spec(art["device_kind"]),
        ideal_bytes_global=art.get("ideal_bytes", 0.0),
        async_coll_bytes_per_chip=art.get("async_collective_bytes", {}),
    )


def format_table(rows: list[RooflineTerms]) -> str:
    hdr = (
        f"{'arch':22s} {'shape':12s} {'mesh':6s} "
        f"{'compute_s':>10s} {'memory_s':>10s} {'collect_s':>10s} "
        f"{'bound':>10s} {'useful%':>8s} {'roofline%':>9s}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:22s} {r.shape:12s} {r.mesh:6s} "
            f"{r.compute_s:10.4g} {r.memory_s:10.4g} {r.collective_s:10.4g} "
            f"{r.dominant:>10s} {100*r.useful_flops_fraction:8.1f} "
            f"{100*r.roofline_fraction:9.1f}"
        )
    return "\n".join(lines)


__all__ = [
    "collective_bytes",
    "collective_bytes_split",
    "RooflineTerms",
    "model_flops",
    "from_artifact",
    "format_table",
]
