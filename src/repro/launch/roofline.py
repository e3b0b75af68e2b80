"""Roofline-term extraction from compiled dry-run artifacts.

The container has no TPU, so we derive the three roofline terms from the
compiled HLO (per the assignment):

    compute    = HLO_FLOPs       / (chips * peak_FLOPs)
    memory     = HLO_bytes       / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are
parsed from the post-SPMD optimized HLO text (sum of output-shape bytes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute /
ragged-all-to-all ops).  Hardware constants come from ``PEAKS``, keyed by
the device kind JAX reports.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional



@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # B/s
    ici_bw: float               # B/s per interconnect link


#: Per-chip peaks keyed by ``jax.devices()[0].device_kind``.  TPU v5e
#: (device kind ``TPU v5 lite``): Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
#: interconnect over 4 links (50 GB/s each).
PEAKS = {
    'TPU v5 lite': Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f'no peaks for device kind {device_kind!r}; the '
                       f'table has {sorted(PEAKS)}')
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    'pred': 1, 's8': 1, 'u8': 1, 'f8e4m3fn': 1, 'f8e5m2': 1,
    's16': 2, 'u16': 2, 'bf16': 2, 'f16': 2,
    's32': 4, 'u32': 4, 'f32': 4,
    's64': 8, 'u64': 8, 'f64': 8, 'c64': 8, 'c128': 16,
}

_COLLECTIVES = ('all-gather', 'all-reduce', 'reduce-scatter', 'all-to-all',
                'collective-permute', 'ragged-all-to-all')

# e.g.  %all-reduce.5 = f32[16,1024]{1,0} all-reduce(...)
_OP_RE = re.compile(
    r'=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s(' +
    '|'.join(_COLLECTIVES) + r')\(')
# tuple-result collectives:  = (f32[..], f32[..]) all-reduce(
_TUPLE_RE = re.compile(
    r'=\s*\(([^)]*)\)\s*(' + '|'.join(_COLLECTIVES) + r')\(')
_SHAPE_RE = re.compile(r'([a-z0-9]+)\[([0-9,]*)\]')


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(','):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes per collective kind over the optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _OP_RE.search(stripped)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _shape_bytes(dtype, dims)
            counts[kind] += 1
            continue
        m = _TUPLE_RE.search(stripped)
        if m:
            shapes, kind = m.groups()
            for dtype, dims in _SHAPE_RE.findall(shapes):
                out[kind] += _shape_bytes(dtype, dims)
            counts[kind] += 1
    return {'bytes': out, 'counts': counts,
            'total_bytes': sum(out.values())}


@dataclasses.dataclass
class Roofline:
    flops: float                # total HLO FLOPs (whole program, all chips)
    hbm_bytes: float            # total bytes accessed
    coll_bytes: float           # total collective bytes (per-chip shapes)
    chips: int
    device_kind: str            # key of ``PEAKS``
    model_flops: float = 0.0    # 6*N*D useful-FLOPs estimate

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * peaks(self.device_kind).flops_bf16)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * peaks(self.device_kind).hbm_bw)

    @property
    def t_collective(self) -> float:
        # HLO shapes are already per-chip after SPMD partitioning
        return self.coll_bytes / peaks(self.device_kind).ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {'compute': self.t_compute, 'memory': self.t_memory,
                 'collective': self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> Optional[float]:
        if self.model_flops and self.flops:
            return self.model_flops / self.flops
        return None

    def as_dict(self) -> dict:
        return {
            'flops': self.flops, 'hbm_bytes': self.hbm_bytes,
            'coll_bytes': self.coll_bytes, 'chips': self.chips,
            'device_kind': self.device_kind,
            't_compute_s': self.t_compute, 't_memory_s': self.t_memory,
            't_collective_s': self.t_collective,
            'bottleneck': self.bottleneck,
            'model_flops': self.model_flops,
            'useful_ratio': self.useful_ratio,
        }


def model_flops_estimate(n_active_params: int, tokens: int,
                         kind: str) -> float:
    """MODEL_FLOPS = 6 * N * D for training, 2 * N * D for inference
    (N = active params for MoE)."""
    mult = 6.0 if kind == 'train' else 2.0
    return mult * n_active_params * tokens


def from_compiled(compiled, lowered_text: str, chips: int, device_kind: str,
                  model_flops: float = 0.0) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get('flops', 0.0))
    byt = float(cost.get('bytes accessed', 0.0))
    coll = collective_bytes(lowered_text)
    return Roofline(flops=flops, hbm_bytes=byt,
                    coll_bytes=float(coll['total_bytes']), chips=chips,
                    device_kind=device_kind, model_flops=model_flops)
